"""Plain references for what the timed path produces.

Written from the paper's description (TreeCSS, arXiv:2408.01691 §4.1,
§4.2, §5.1) in numpy and plain ``jax.numpy``; nothing here imports the
program or takes anything it made, except the answers being checked.

- Tree-MPSI: volume-aware pairing and exact set intersection (numpy).
- Alignment to rows: row i of the training split carries the label
  owner's id ``sets[0][i]``.
- Cluster-Coreset steps 2, 4 and 5 (rank weights, (CT, label) groups,
  argmin of the summed distance) from a clustering, and three checks of
  a clustering itself (step 1), in float64.
- The split MLP of §5.1 trained by mini-batch Adam on the Eq. (2)
  weighted loss, in float32 at the matrix-product precision the
  configuration states; ``dtype="bfloat16"`` computes everything in
  bfloat16 instead (the control).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

# ------------------------------------------------------------- alignment


def intersect(sets: Sequence[np.ndarray]) -> np.ndarray:
    return functools.reduce(np.intersect1d, [np.unique(s) for s in sets])


def tree_rounds(sets: Sequence[np.ndarray], protocol: str = "oprf"
                ) -> List[List[Tuple[int, int, np.ndarray]]]:
    """Tree-MPSI with volume-aware pairing (paper §4.1): each round sorts
    the active parties by how many ids they hold, pairs the k-th with the
    (k + ceil(U/2))-th, and the receiver (the larger side under OPRF, the
    smaller under RSA; the later party on a tie) keeps the pair's
    intersection.  Returns per round [(sender, receiver, intersection)]."""
    holdings: Dict[int, np.ndarray] = {i: np.unique(s)
                                       for i, s in enumerate(sets)}
    active = list(range(len(sets)))
    rounds = []
    while len(active) > 1:
        order = sorted(active, key=lambda c: len(holdings[c]))
        half = math.ceil(len(order) / 2)
        out = []
        for k in range(len(order) // 2):
            a, b = order[k], order[k + half]
            small, big = (a, b) if len(holdings[a]) <= len(holdings[b]) \
                else (b, a)
            sender, receiver = (small, big) if protocol == "oprf" \
                else (big, small)
            out.append((sender, receiver,
                        np.intersect1d(holdings[sender], holdings[receiver])))
        for _, receiver, inter in out:
            holdings[receiver] = inter
        nxt = [r for _, r, _ in out]
        if len(order) % 2:
            nxt.append(order[half - 1])
        active = nxt
        rounds.append(out)
    return rounds


def aligned_rows(label_owner_ids: np.ndarray, inter: np.ndarray
                 ) -> np.ndarray:
    """Training rows (ascending) whose ids are in the intersection."""
    return np.flatnonzero(np.isin(label_owner_ids, inter))


# --------------------------------------------------------------- coreset


def _ed(sq_dist: np.ndarray) -> np.ndarray:
    """Euclidean distance of a float32 squared distance, in float32."""
    return np.sqrt(np.maximum(sq_dist.astype(np.float32), np.float32(0)))


def rank_weights(assign: np.ndarray, sq_dist: np.ndarray, k: int
                 ) -> np.ndarray:
    """Step 2: w_i = pos(ed_i, DeSort(ed of i's cluster)) / |cluster|,
    one cluster at a time; ties keep row order."""
    ed = _ed(sq_dist)
    w = np.zeros(assign.shape[0], np.float64)
    for c in range(k):
        rows = np.flatnonzero(assign == c)
        if rows.size == 0:
            continue
        desc = rows[np.argsort(-ed[rows], kind="stable")]
        w[desc] = np.arange(1, rows.size + 1) / rows.size
    return w.astype(np.float32)


def select_coreset(assigns: Sequence[np.ndarray],
                   sq_dists: Sequence[np.ndarray], labels: np.ndarray,
                   k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Steps 4-5: group rows by (cluster of every party, label), keep the
    row of least summed distance in each group (the first on a tie), and
    weight it by the sum of its parties' rank weights.  Distances are the
    float32 ones of the clustering, summed party by party in float32."""
    ed = functools.reduce(np.add, [_ed(s) for s in sq_dists])
    w = [rank_weights(a, s, k) for a, s in zip(assigns, sq_dists)]
    best: Dict[tuple, int] = {}
    for i in range(labels.shape[0]):
        key = tuple(int(a[i]) for a in assigns) + (int(labels[i]),)
        j = best.get(key)
        if j is None or ed[i] < ed[j]:
            best[key] = i
    idx = np.sort(np.fromiter(best.values(), np.int64))
    return idx, functools.reduce(np.add, [wm[idx] for wm in w])


def nearest(points: np.ndarray, centroids: np.ndarray, dtype: str
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Step 1's assignment from given centroids, every array in ``dtype``:
    each point's nearest centroid (the first on a tie) and its squared
    distance, by the expansion ``|x|^2 - 2 x.c + |c|^2`` clamped at 0."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(points, dtype)
    c = jnp.asarray(centroids, dtype)
    with jax.default_matmul_precision("highest"):
        d2 = (jnp.sum(x * x, 1)[:, None]
              - jnp.asarray(2, dtype) * jnp.dot(x, c.T,
                                                preferred_element_type=dtype)
              + jnp.sum(c * c, 1)[None])
    d2 = jnp.maximum(d2, jnp.asarray(0, dtype))
    return (np.asarray(jnp.argmin(d2, axis=1), np.int32),
            np.asarray(jnp.min(d2, axis=1), np.float32))


def clustering_gaps(points: np.ndarray, assign: np.ndarray,
                    sq_dist: np.ndarray, centroids: np.ndarray
                    ) -> Dict[str, float]:
    """Three checks of one party's k-means answer, in float64, each as a
    share of the mean squared distance of a point to its centroid:

    - ``assign``: the widest amount by which a point's assigned centroid
      lies farther than its nearest one;
    - ``sq_dist``: the widest error of a reported squared distance;
    - ``lloyd``: the widest distance (squared) between a centroid and the
      mean of the points assigned to it, which a converged Lloyd fit
      keeps small and an unmoved one does not."""
    x = points.astype(np.float64)
    c = centroids.astype(np.float64)
    d2 = (np.sum(x * x, 1)[:, None] - 2 * x @ c.T
          + np.sum(c * c, 1)[None])
    d2 = np.maximum(d2, 0.0)
    rows = np.arange(x.shape[0])
    scale = max(float(np.mean(d2.min(axis=1))), 1e-30)
    mine = d2[rows, assign]
    lloyd = 0.0
    for k in range(c.shape[0]):
        member = assign == k
        if member.any():
            mu = x[member].mean(axis=0)
            lloyd = max(lloyd, float(np.sum((mu - c[k]) ** 2)))
    return {"assign": float(np.max(mine - d2.min(axis=1))) / scale,
            "sq_dist": float(np.max(np.abs(sq_dist - mine))) / scale,
            "lloyd": lloyd / scale}


# --------------------------------------------------------------- training


def _model_init(seed: int, widths: Sequence[int], bottom: int, hidden: int,
                n_out: int, dtype):
    """§5.1's split MLP, initialised from the seed: per party a dense
    ReLU bottom (d_m -> bottom), a top MLP (M*bottom -> hidden -> n_out);
    weights N(0, 1/fan_in) from ``PRNGKey(seed)`` split M+2 ways, biases 0."""
    import jax
    import jax.numpy as jnp

    m = len(widths)
    ks = jax.random.split(jax.random.PRNGKey(seed), m + 2)
    params = {"bottoms": [], "top": {}}
    for i, d in enumerate(widths):
        params["bottoms"].append({
            "w": jax.random.normal(ks[i], (d, bottom), jnp.float32)
            * (d ** -0.5), "b": jnp.zeros((bottom,), jnp.float32)})
    params["top"] = {
        "w1": jax.random.normal(ks[m], (m * bottom, hidden), jnp.float32)
        * ((m * bottom) ** -0.5),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jax.random.normal(ks[m + 1], (hidden, n_out), jnp.float32)
        * (hidden ** -0.5),
        "b2": jnp.zeros((n_out,), jnp.float32)}
    return jax.tree_util.tree_map(lambda t: t.astype(dtype), params)


def forward(params, xs):
    import jax
    import jax.numpy as jnp

    acts = [jax.nn.relu(x @ p["w"] + p["b"])
            for p, x in zip(params["bottoms"], xs)]
    h = jax.nn.relu(jnp.concatenate(acts, axis=1) @ params["top"]["w1"]
                    + params["top"]["b1"])
    return h @ params["top"]["w2"] + params["top"]["b2"]


def _weighted_bce(logits, y, w):
    import jax.numpy as jnp

    ce = (jnp.maximum(logits, 0) - logits * y
          + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return jnp.sum(w * ce) / jnp.maximum(jnp.sum(w), 1e-12)


@functools.lru_cache(maxsize=4)
def _epoch_fn(lr: float, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    b1, b2, eps = 0.9, 0.999, 1e-8

    def loss_fn(p, xs, y, w):
        return _weighted_bce(forward(p, xs)[:, 0], y, w)

    def epoch(params, mu, nu, t, xs_all, y_all, w_all, idx, mask):
        def step(carry, sched):
            p, m_, v_, t_ = carry
            ib, mb = sched
            xs = [x[ib] for x in xs_all]
            loss, g = jax.value_and_grad(loss_fn)(p, xs, y_all[ib],
                                                  w_all[ib] * mb)
            t_ = t_ + 1
            bc1 = (1 - b1 ** t_).astype(dtype)
            bc2 = (1 - b2 ** t_).astype(dtype)
            m_ = jax.tree_util.tree_map(
                lambda a, b: (b1 * a + (1 - b1) * b).astype(dtype), m_, g)
            v_ = jax.tree_util.tree_map(
                lambda a, b: (b2 * a + (1 - b2) * b * b).astype(dtype),
                v_, g)
            p = jax.tree_util.tree_map(
                lambda a, m1, v1: (a - lr * (m1 / bc1)
                                   / (jnp.sqrt(v1 / bc2) + eps)
                                   ).astype(dtype), p, m_, v_)
            return (p, m_, v_, t_), (loss, g)

        (params, mu, nu, t), (losses, grads) = jax.lax.scan(
            step, (params, mu, nu, t), (idx, mask))
        first_grad = jax.tree_util.tree_map(lambda a: a[0], grads)
        return params, mu, nu, t, jnp.mean(losses.astype(jnp.float32)), \
            first_grad

    return jax.jit(epoch)


def train(xs: Sequence[np.ndarray], y: np.ndarray, w: np.ndarray, *,
          seed: int, epochs: int, batch: int, lr: float, bottom: int,
          hidden: int, precision: str, dtype: str = "float32",
          half_batch: bool = False):
    """Mini-batch Adam (b1 0.9, b2 0.999, eps 1e-8, bias-corrected) on
    the weighted loss, ``epochs`` epochs, each over a fresh permutation
    from ``numpy.random.default_rng(seed)`` in batches of ``batch`` rows
    (the last one short), matrix products at ``precision`` (JAX's name:
    "default" is one bfloat16 pass on a TPU).  ``half_batch`` is a
    planted fault: the second half of every batch is left out and the
    mean taken over the rest.  Returns (params0, params, per-epoch mean
    loss, first step's gradient)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = int(y.shape[0])
    bs = min(batch, n)
    steps = -(-n // bs)
    widths = [x.shape[1] for x in xs]
    p0 = _model_init(seed, widths, bottom, hidden, 1, dt)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    params, mu, nu = p0, zeros, zeros
    t = jnp.zeros((), jnp.float32)
    xs_d = [jnp.asarray(x, dt) for x in xs]
    y_d = jnp.asarray(y, dt)
    w_d = jnp.asarray(w, dt)
    fn = _epoch_fn(float(lr), dt.name)
    rng = np.random.default_rng(seed)
    losses, g0 = [], None
    with jax.default_matmul_precision(precision):
        for _ in range(epochs):
            flat = np.zeros(steps * bs, np.int32)
            flat[:n] = rng.permutation(n)
            mask = np.zeros(steps * bs, np.float32)
            mask[:n] = 1.0
            if half_batch:
                mask.reshape(steps, bs)[:, bs // 2:] = 0.0
            params, mu, nu, t, loss, g = fn(
                params, mu, nu, t, xs_d, y_d, w_d,
                jnp.asarray(flat.reshape(steps, bs)),
                jnp.asarray(mask.reshape(steps, bs), dt))
            losses.append(float(loss))
            g0 = g if g0 is None else g0
    return p0, params, losses, g0


def predict_logits(params, xs: Sequence[np.ndarray], *, precision: str,
                   block: int = 8192) -> np.ndarray:
    """Forward over rows in blocks at the parameters' dtype and
    ``precision``."""
    import jax
    import jax.numpy as jnp

    dt = params["top"]["w1"].dtype
    out = []
    with jax.default_matmul_precision(precision):
        for s in range(0, xs[0].shape[0], block):
            out.append(np.asarray(forward(
                params, [jnp.asarray(x[s:s + block], dt) for x in xs]),
                np.float32))
    return np.concatenate(out)[:, 0]


def leaves(params) -> Dict[str, np.ndarray]:
    """Flat ``{path: float64 array}`` view of a split-MLP param tree."""
    out = {}
    for i, b in enumerate(params["bottoms"]):
        for k in ("w", "b"):
            out[f"bottoms.{i}.{k}"] = np.asarray(b[k], np.float64)
    for k in ("w1", "b1", "w2", "b2"):
        out[f"top.{k}"] = np.asarray(params["top"][k], np.float64)
    return out
