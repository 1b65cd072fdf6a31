"""Cluster-Coreset (paper §4.2): clustering-based multi-party coreset
selection with distance-rank sample weighting.

Five steps, implemented exactly as the paper:
  1. Local clustering    — each client K-Means its local feature slice.
  2. Weight computation  — w_i^m = pos(ed_i, DeSort({ed_j})) / |S_c|
                           (closer to centroid → later in the descending
                           sort → larger pos → higher weight).
  3. CT construction     — clients ship HE-encrypted (w_i^m, c_i^m, ed_i^m)
                           per sample via the aggregation server; the label
                           owner assembles CT_i = (c_i^1..c_i^M).
  4. Data selection      — group by (CT, label); keep argmin_i Σ_m ed_i^m
                           per group.
  5. Sample weighting    — coreset weight w_i = Σ_m w_i^m, used by the
                           Eq.(2) weighted loss during training.

The HE exchange (step 3/4 transport) is exercised through
``repro.core.he`` with packed fixed-point tuples; ``use_he=False`` skips
crypto (identical selection, used by large benchmarks) while still
counting the bytes that WOULD be shipped.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import he
from repro.core.kmeans import kmeans, kmeans_fit
from repro.obs.trace import span
from repro.data.vertical import VerticalPartition
from repro.sharding import batch_shard_map, pad_batch_rows, \
    resolve_batch_mesh


@dataclasses.dataclass
class ClientClustering:
    """Step 1+2 output for one client."""
    assign: np.ndarray        # (N,) int32 cluster index c_i^m
    sq_dist: np.ndarray       # (N,) f32  squared distance
    weight: np.ndarray        # (N,) f32  local weight w_i^m
    centroids: np.ndarray     # (k, d_m)


@dataclasses.dataclass
class CoresetResult:
    indices: np.ndarray       # [N_core] indices into the aligned samples
    weights: np.ndarray       # (N_core,) f32 — Σ_m w_i^m
    n_groups: int             # distinct (CT, label) groups
    comm_bytes: int           # step-3/4 traffic through the server
    he_seconds: float         # measured encryption time (0 if use_he=False)
    local: List[ClientClustering]
    # steps 1-2 run CONCURRENTLY on the clients in a real deployment —
    # the stage cost is the max over clients, not the host-measured sum
    per_client_seconds: List[float] = dataclasses.field(default_factory=list)
    select_seconds: float = 0.0
    batched: bool = False     # clients fit via one vmap'd device call
    shards: int = 1           # mesh-axis size the client batch split over

    @property
    def makespan_seconds(self) -> float:
        return (max(self.per_client_seconds, default=0.0)
                + self.select_seconds + self.he_seconds)


def rank_weights(assign: np.ndarray, sq_dist: np.ndarray,
                 k: int) -> np.ndarray:
    """Step-2 weights, vectorized: w_i = pos(ed_i, DeSort({ed_j})) / |S_c|.

    One lexsort groups samples by cluster with distances descending inside
    each group (DeSort); the 1-based position within the group divided by
    the group size is the weight — the closest sample gets pos = |S_c| →
    weight 1, the farthest gets 1/|S_c|. Stable, so ties break by
    original index exactly like the per-cluster loop it replaces.
    """
    n = assign.shape[0]
    if n == 0:
        return np.zeros(0, np.float32)
    ed = np.sqrt(np.maximum(sq_dist, 0.0))
    # primary key: cluster; secondary: descending distance (stable ties)
    order = np.lexsort((-ed, assign))
    sizes = np.bincount(assign, minlength=k)
    starts = np.concatenate(([0], np.cumsum(sizes)[:-1]))
    sorted_assign = assign[order]
    pos = np.arange(1, n + 1) - starts[sorted_assign]      # 1-based in-group
    weight = np.zeros(n, np.float64)
    weight[order] = pos / sizes[sorted_assign]
    return weight.astype(np.float32)


def local_cluster_weights(features: np.ndarray, k: int, *, seed: int = 0,
                          iters: int = 25, impl: str = "ref",
                          algo: str = "lloyd") -> ClientClustering:
    """Steps 1-2 on one client's feature slice."""
    n = features.shape[0]
    k_eff = int(min(k, n))
    cents, assign, sqd = kmeans(features, k_eff, seed=seed, iters=iters,
                                impl=impl, algo=algo)
    assign = assign.astype(np.int32)
    weight = rank_weights(assign, sqd, k_eff)
    return ClientClustering(assign, sqd.astype(np.float32), weight, cents)


def _ct_keys(assigns: Sequence[np.ndarray]) -> np.ndarray:
    """Stack per-client cluster indices into CT rows (N, M)."""
    return np.stack(assigns, axis=1)


def _group_ids(keys: np.ndarray) -> np.ndarray:
    """``np.unique(keys, axis=0, return_inverse=True)[1]`` for an (N, C)
    integer key matrix, through one int64 code per row: each column,
    less its least value, is a digit of a mixed radix with the first
    column most significant, so the codes sort as the rows do.  A 1-D
    unique is ~25x faster than the row-wise one at 250K rows.  Keys too
    wide for one int64 take the row-wise unique."""
    if keys.shape[0] == 0:
        return np.zeros(0, np.int64)
    lo = keys.min(axis=0)
    spans = [int(v) + 1 for v in keys.max(axis=0) - lo]
    if math.prod(spans) >= 2 ** 63:
        return np.unique(keys, axis=0, return_inverse=True)[1].reshape(-1)
    code = np.zeros(keys.shape[0], np.int64)
    for col, radix in enumerate(spans):
        code = code * radix + (keys[:, col] - lo[col])
    return np.unique(code, return_inverse=True)[1].reshape(-1)


def select_coreset(local: Sequence[ClientClustering], labels: np.ndarray, *,
                   regression_bins: int = 16) -> Tuple[np.ndarray, np.ndarray,
                                                       int]:
    """Steps 4-5 at the label owner. Returns (indices, weights, n_groups).

    Regression labels (float) are quantile-binned so "split S_ct^j by label"
    stays meaningful — the paper trains LinearReg with the same machinery.
    Spans: ``coreset.bin`` (regression only) and ``coreset.group``.
    """
    cts = _ct_keys([c.assign for c in local])                  # (N, M)
    ed = np.stack([np.sqrt(np.maximum(c.sq_dist, 0.0)) for c in local],
                  axis=1)                                      # (N, M)
    w = np.stack([c.weight for c in local], axis=1)            # (N, M)

    if np.issubdtype(labels.dtype, np.floating):
        with span("coreset.bin", rows=labels.shape[0], bins=regression_bins):
            qs = np.quantile(labels,
                             np.linspace(0, 1, regression_bins + 1)[1:-1])
            lab = np.searchsorted(qs, labels).astype(np.int64)
    else:
        lab = labels.astype(np.int64)

    group_sp = span("coreset.group", rows=labels.shape[0])
    with group_sp:
        keys = np.concatenate([cts, lab[:, None]], axis=1)     # (N, M+1)
        group_ids = _group_ids(keys)
        agg_ed = ed.sum(axis=1)

        n_groups = int(group_ids.max()) + 1 if group_ids.size else 0
        # argmin aggregated distance per group
        order = np.lexsort((agg_ed, group_ids))
        first = np.ones(len(order), bool)
        first[1:] = group_ids[order][1:] != group_ids[order][:-1]
        chosen = np.sort(order[first])
        weights = w[chosen].sum(axis=1)
    group_sp.set(groups=n_groups, kept=int(chosen.shape[0]))
    return chosen.astype(np.int64), weights.astype(np.float32), n_groups


def _he_exchange_cost(local: Sequence[ClientClustering], n: int,
                      use_he: bool) -> Tuple[int, float]:
    """Step-3 transport: one packed ciphertext (w, c, ed) per sample per
    client, plus the encrypted selected-indicator broadcast."""
    m = len(local)
    if not use_he:
        return n * m * 3 * 8, 0.0
    pk, sk = he.keygen(256, seed=11)
    t0 = time.perf_counter()
    n_sample = min(n, 64)
    for cl in local:
        for i in range(n_sample):
            c = he.encrypt_tuple(pk, [float(cl.weight[i]),
                                      float(cl.assign[i]),
                                      float(np.sqrt(max(cl.sq_dist[i], 0)))])
    t = time.perf_counter() - t0
    # verified-sample decrypt round trip (fidelity check)
    vals = he.decrypt_tuple(sk, c, 3)
    est = t * (n / max(n_sample, 1))
    return n * m * pk.ciphertext_bytes(), est


def clients_batchable(features: Sequence[np.ndarray], *,
                      algo: str = "lloyd",
                      batch_clients: str = "auto",
                      clusters: Optional[int] = None) -> bool:
    """True when steps 1-2 will run through the vmap'd batched path.

    Same-shape clients always batch; ragged (unequal ``(N, d_m)``)
    clients batch through the pad-and-mask path UNLESS some client has
    fewer samples than ``clusters`` — that client would need its own
    smaller k (k is static under vmap), so those fall back to the
    sequential loop."""
    feats = list(features)
    if batch_clients == "never" or algo != "lloyd" or len(feats) <= 1:
        return False
    if len({f.shape for f in feats}) == 1:
        return True
    min_n = min(f.shape[0] for f in feats)
    return min_n >= 1 and (clusters is None or min_n >= clusters)


def _fit_batch(keys, points, *, fit, k: int, iters: int, impl: str):
    """Same-shape clients: one vmap'd ``fit`` over (M, N, d)."""
    return jax.vmap(functools.partial(
        fit, k=k, iters=iters, impl=impl))(keys, points)


def _fit_batch_ragged(keys, points, n_valid, *, fit, k: int, iters: int,
                      impl: str):
    """Ragged clients: rows at and past each client's ``n_valid`` are
    zero padding, masked inside ``fit``."""
    one = lambda kk, p, nv: fit(kk, p, k, iters=iters, impl=impl,
                                n_valid=nv)
    return jax.vmap(one)(keys, points, n_valid)


@functools.lru_cache(maxsize=8)
def _fit_program(fit, ragged: bool, k: int, iters: int, impl: str, mesh,
                 axis: Optional[str],
                 arg_specs: Tuple[Tuple[Tuple[int, ...], np.dtype], ...]):
    """The AOT-compiled batched k-means program, one per (per-client
    ``fit`` function, layout, k, iters, impl, resolved mesh/axis,
    argument shapes and dtypes): every key is hashable, so repeated
    ``cluster_coreset`` calls on the same deployment reuse one
    executable instead of lowering and compiling it again, and a
    different ``fit`` never reuses another's program.  Bounded at 8 (a
    process sees one or two layouts); ``clear_fit_cache`` releases the
    executables and the Mesh objects their keys pin."""
    body = _fit_batch_ragged if ragged else _fit_batch
    fn = functools.partial(body, fit=fit, k=k, iters=iters, impl=impl)
    if mesh is not None:
        fn = batch_shard_map(fn, mesh, axis)
    specs = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in arg_specs]
    return jax.jit(fn).lower(*specs).compile()


def clear_fit_cache() -> None:
    """Drop every cached batched k-means executable (and the Mesh
    objects their keys pin); ``repro.train.vfl.clear_program_caches``
    calls it, next to the training-side caches."""
    _fit_program.cache_clear()


def _batched_local_clusterings(features: Sequence[np.ndarray], k: int, *,
                               seed: int, iters: int, impl: str,
                               mesh=None,
                               shard_axis: Optional[str] = None
                               ) -> Tuple[List[ClientClustering], float,
                                          int]:
    """Steps 1-2 for ALL clients in one vmap'd device call.

    Client slices stack into an (M, N, d) batch and run through a single
    ``jax.vmap``'d ``kmeans_fit`` — one XLA program instead of M
    sequential host dispatches, with per-client PRNG keys matching the
    sequential path's ``seed + 17*m`` schedule. Weight ranking stays on
    host (cheap, O(N log N) per client).

    Ragged clients pad to (max N, max d): zero-padded feature columns
    are exact (zero diffs add exact +0.0 to every distance and centroid
    update), zero-padded rows are masked via ``kmeans_fit(n_valid=)``
    (see its docstring), and each client's outputs slice back to its
    true (N_m, d_m).

    With ``mesh``, the client batch additionally shards over one mesh
    axis via ``shard_map`` (DESIGN.md §5): M pads to a multiple of the
    axis size with row-0 filler and each device fits M/axis clients —
    the per-client program is unchanged, so results stay byte-identical
    to the single-device batch.

    The program comes from ``_fit_program``: the first call with a
    given key compiles it, later calls look it up (the
    ``coreset.compile`` span covers the lookup and records
    ``cache_hit``).  Returns (clusterings, seconds, n_shards) where
    seconds excludes that lookup and any compile.
    """
    m = len(features)
    ns = [int(f.shape[0]) for f in features]
    ds = [int(f.shape[1]) for f in features]
    n_max, d_max = max(ns), max(ds)
    ragged = len({f.shape for f in features}) > 1
    k_eff = int(min(k, min(ns)))
    with span("coreset.pack", clients=m, rows=n_max, features=d_max,
              ragged=ragged):
        keys = np.stack([np.asarray(jax.random.PRNGKey(seed + 17 * i))
                         for i in range(m)])
        if ragged:
            stacked = np.zeros((m, n_max, d_max), np.float32)
            for i, f in enumerate(features):
                stacked[i, :ns[i], :ds[i]] = f
            args: Sequence[np.ndarray] = (keys, stacked,
                                          np.asarray(ns, np.int32))
        else:
            args = (keys, np.stack(features).astype(np.float32))
        mesh, axis, n_shards = resolve_batch_mesh(mesh, shard_axis)
        if mesh is not None:
            args, _ = pad_batch_rows(args, n_shards)
    with span("coreset.compile", clients=m, k=k_eff,
              iters=iters) as compile_sp:
        hits = _fit_program.cache_info().hits
        compiled = _fit_program(kmeans_fit, ragged, k_eff, iters, impl,
                                mesh, axis,
                                tuple((a.shape, a.dtype) for a in args))
        compile_sp.set(
            cache_hit=int(_fit_program.cache_info().hits > hits))
    t0 = time.perf_counter()
    cents, assign, sqd = jax.block_until_ready(compiled(*args))
    t_exec = time.perf_counter() - t0
    with span("coreset.weights", clients=m):
        cents, assign, sqd = (np.asarray(cents), np.asarray(assign),
                              np.asarray(sqd))
        local = [
            ClientClustering(assign[i, :ns[i]].astype(np.int32),
                             sqd[i, :ns[i]].astype(np.float32),
                             rank_weights(assign[i, :ns[i]],
                                          sqd[i, :ns[i]], k_eff),
                             cents[i][:, :ds[i]])
            for i in range(m)
        ]
    return local, t_exec, n_shards


def cluster_coreset(partition: VerticalPartition, clusters_per_client: int, *,
                    seed: int = 0, kmeans_iters: int = 25,
                    kmeans_impl: str = "ref", use_he: bool = False,
                    kmeans_algo: str = "lloyd",
                    batch_clients: str = "auto",
                    mesh=None,
                    shard_axis: Optional[str] = None) -> CoresetResult:
    """Full Cluster-Coreset over a vertical partition.

    ``batch_clients``: "auto" runs all clients through one vmap'd fit
    (Lloyd only) — same-shape slices directly, ragged slices through the
    pad-and-mask path; "never" forces the sequential per-client host
    loop. The batched device call computes all M fits at once, so its
    wall-clock / M approximates ONE client's concurrent compute —
    recorded per client to keep ``makespan_seconds`` on the documented
    max-over-clients model.  ``mesh`` shards the client batch over one
    mesh axis (``shard_axis`` or the mesh's data axis — a 2-D
    ``(data, model)`` train mesh replicates over ``model``) so CSS
    scales past single-device memory; selection stays byte-identical.
    ``kmeans_algo="minibatch"`` (the beyond-paper large-client path)
    now gathers each Sculley minibatch INSIDE the update kernel
    (``kmeans_update(idx=)``, scalar-prefetched indices — DESIGN.md
    §8), dropping the per-iteration ``points[idx]`` HBM round trip.
    """
    feats = list(partition.client_features)
    n_shards = 1
    batchable = clients_batchable(feats, algo=kmeans_algo,
                                  batch_clients=batch_clients,
                                  clusters=clusters_per_client)
    with span("coreset.fit", clients=len(feats), batched=batchable,
              k=clusters_per_client, algo=kmeans_algo) as fit_sp:
        if batchable:
            local, t_exec, n_shards = _batched_local_clusterings(
                feats, clusters_per_client, seed=seed, iters=kmeans_iters,
                impl=kmeans_impl, mesh=mesh, shard_axis=shard_axis)
            per_client = [t_exec / len(feats)] * len(feats)
        else:
            local = []
            per_client = []
            for m, f in enumerate(feats):
                t0 = time.perf_counter()
                local.append(local_cluster_weights(
                    f, clusters_per_client, seed=seed + 17 * m,
                    iters=kmeans_iters, impl=kmeans_impl, algo=kmeans_algo))
                per_client.append(time.perf_counter() - t0)
        fit_sp.set(shards=n_shards)
    sel_sp = span("coreset.select", rows=partition.n_samples)
    with sel_sp:
        t0 = time.perf_counter()
        idx, w, n_groups = select_coreset(local, partition.labels)
        select_secs = time.perf_counter() - t0
    sel_sp.set(n_coreset=int(idx.shape[0]), n_groups=n_groups)
    he_sp = span("coreset.he", use_he=use_he, clients=len(feats))
    with he_sp:
        comm, he_secs = _he_exchange_cost(local, partition.n_samples, use_he)
    he_sp.set(comm_bytes=comm)
    return CoresetResult(indices=idx, weights=w, n_groups=n_groups,
                         comm_bytes=comm, he_seconds=he_secs, local=local,
                         per_client_seconds=per_client,
                         select_seconds=select_secs, batched=batchable,
                         shards=n_shards)
