"""Batched PSI round executor — the device half of TPSI (DESIGN.md §6).

The host protocol layer (repro.core.tpsi / mpsi) keeps everything that
is inherently sequential bigint work (RSA blind/sign/unblind) or wire
accounting; this engine takes the data-parallel remainder of every
concurrent pair of an MPSI round — OPRF tag evaluation and sorted-merge
intersection — pads all pairs to one (pairs, P) batch, and runs them as
vmapped device dispatches:

  oprf_round  : ids --psi_prf kernel--> 62-bit tags --sort-->
                --sorted_intersect kernel--> matched receiver ids
  match_round : host-computed tags (e.g. truncated RSA signatures)
                --sort--> --sorted_intersect kernel--> matched ids

so a 10-client Tree-MPSI costs O(log m) dispatches instead of ~45
per-element Python sessions.  Byte/message accounting is NOT done here —
both backends share the cost model in repro.core.tpsi, which keeps the
modeled wire costs byte-identical across backends.

Sorting between tag-eval and merge is mode-switched (``sort=``):

  "device"  one dispatch per round; tags are sorted in-graph with
            ``lax.sort`` — the TPU-true path (device sort is cheap on
            real hardware and ids never leave the accelerator).
  "host"    two dispatches (tag-eval, then merge) with numpy's radix-
            class u64 sort between them — the fast path on CPU, where
            XLA's multi-operand comparator sort is ~30× slower than
            numpy.  Default keys off the actual platform
            (``jax.default_backend()``): a CPU backend gets "host"
            whether or not the Pallas interpreter is on; accelerators
            get "device".

Sharding (``mesh=``): a round's (pairs, P) batch can split over one
mesh axis — ``shard_axis`` or the mesh's data axis — via ``shard_map``
(DESIGN.md §5).  The pair batch pads to a multiple of the axis size
(row-0 filler, outputs truncated) and each device runs the identical
per-pair program on its slice, so intersections stay byte-identical to
the single-device path while per-device memory drops by the axis size.

Id recovery uses the merge kernel's (sel, rank) outputs: ``rank`` is
the receiver-element count in merged order, so a selected slot's id is
``receiver_ids_by_tag[rank - 1]`` — no payload lanes ride the merge and
no compaction sort is needed (see kernels/sorted_intersect/ref.py).

Preconditions: ids are unique per set (tpsi dedups at protocol entry)
and non-negative int64.  Tags live in [0, 2^62): the PRF masks its top
two bits, ``tag_words`` masks host-derived tags, and the packed sort
key (tag << 1) | origin therefore stays below the padding sentinels.

Shapes are static per (pairs, P = next_pow2(max set size)) — jit caches
one executable per bucket.  First use of a bucket compiles OUTSIDE the
timed region (an untimed zeros-input warm-up), so ``EngineRound``
seconds measure protocol execution, not XLA trace/compile; later rounds
and runs that hit the same bucket reuse the cached executable.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.config import ALIGN_ALIASES, AlignOptions, _coerce_options
from repro.kernels.psi_prf.ops import prf_tags
from repro.kernels.sorted_intersect.ops import (next_pow2, pack_keys,
                                                sorted_intersect)
from repro.kernels.sorted_intersect.ref import PAD_A, PAD_B
from repro.obs.trace import span
from repro.sharding import (batch_shard_map, pad_batch_rows, padded_rows,
                            resolve_batch_mesh)

TAG_MASK = (1 << 62) - 1     # engine tag space: 62-bit


def tag_words(x: int) -> int:
    """Map an arbitrary host integer (e.g. an RSA signature) into the
    engine's 62-bit tag space."""
    return x & TAG_MASK


@dataclasses.dataclass
class EngineRound:
    intersections: List[np.ndarray]   # per pair: sorted unique int64 ids
    device_seconds: float             # dispatches + in-between host sort
    dispatches: int = 1
    shards: int = 1                   # mesh-axis size the batch split over


def _default_sort(sort: Optional[str]) -> str:
    """The sort mode the platform actually wants: numpy's radix-class
    u64 sort on a CPU backend (XLA's CPU multi-operand sort is ~30×
    slower), in-graph ``lax.sort`` on accelerators."""
    return sort or ("host" if jax.default_backend() == "cpu" else "device")


# ----------------------------------------------------------- lane packing

def _split64(ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    a = np.asarray(ids, np.int64).astype(np.uint64)
    return ((a >> np.uint64(32)).astype(np.uint32),
            (a & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _pack(sets: Sequence[np.ndarray], p: int
          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """List of (n_i,) int64 -> ((B,P) u32 hi, (B,P) u32 lo, (B,) i32 n)."""
    b = len(sets)
    hi = np.zeros((b, p), np.uint32)
    lo = np.zeros((b, p), np.uint32)
    n = np.zeros((b,), np.int32)
    for i, s in enumerate(sets):
        h, l = _split64(s)
        hi[i, :len(s)] = h
        lo[i, :len(s)] = l
        n[i] = len(s)
    return hi, lo, n


def _host_key_rows(tag64_sorted: np.ndarray, origin: int,
                   pad: Tuple[int, int], p: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Sorted u64 tags -> one padded (P,) u32 key-lane row pair."""
    key = (tag64_sorted.astype(np.uint64) << np.uint64(1)) | np.uint64(origin)
    kh = np.full((p,), pad[0], np.uint32)
    kl = np.full((p,), pad[1], np.uint32)
    kh[:len(key)] = (key >> np.uint64(32)).astype(np.uint32)
    kl[:len(key)] = (key & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return kh, kl


def _batch_counts(sides: Sequence[Sequence[np.ndarray]], rows: int,
                  p: int) -> dict:
    """The ``keys``/``slots`` attributes of an ``align.dispatch`` span,
    from host shapes alone: the real keys of both sides of every pair,
    and the key slots of the batch as dispatched (2 x padded rows x P)."""
    return {"keys": sum(len(s) for side in sides for s in side),
            "slots": 2 * rows * p}


def _mask_pad(kh, kl, n, pad):
    pos = jnp.arange(kh.shape[0], dtype=jnp.int32)
    return (jnp.where(pos < n, kh, np.uint32(pad[0])),
            jnp.where(pos < n, kl, np.uint32(pad[1])))


# ------------------------------------------------------- jitted dispatches

def _prf_batch(r_hi, r_lo, s_hi, s_lo, seeds, *, impl):
    """Tag both sides of every pair: (B,P) id lanes -> (B,P) tag lanes."""
    def one(rh, rl, sh, sl, sd):
        return prf_tags(rh, rl, sd, impl=impl) + prf_tags(sh, sl, sd,
                                                          impl=impl)
    return jax.vmap(one)(r_hi, r_lo, s_hi, s_lo, seeds)


def _merge_batch(a_kh, a_kl, b_kh, b_kl, *, impl):
    """(B,P) pre-sorted key lanes -> (B,2P) (sel, rank)."""
    def one(akh, akl, bkh, bkl):
        sel, rank, _, _ = sorted_intersect(akh, akl, bkh, bkl, impl=impl)
        return sel, rank
    return jax.vmap(one)(a_kh, a_kl, b_kh, b_kl)


def _union_batch(a_kh, a_kl, b_kh, b_kl, *, impl):
    """(B,P) pre-sorted key lanes -> (B,2P) merged (kh, kl) lanes: the
    bitonic merge's *sorted union* of the two sides, pads (both
    sentinels sort past any valid key) collected at the tail.  This is
    the LSM run-compaction primitive of delta-PSI (repro.psi.delta):
    the same ``sorted_intersect`` kernel the intersection path runs,
    read for its merged lanes instead of (sel, rank)."""
    def one(akh, akl, bkh, bkl):
        _, _, m_kh, m_kl = sorted_intersect(akh, akl, bkh, bkl, impl=impl)
        return m_kh, m_kl
    return jax.vmap(one)(a_kh, a_kl, b_kh, b_kl)


def _oprf_single(r_hi, r_lo, r_n, s_hi, s_lo, s_n, seeds, *, impl):
    """Single-dispatch (device-sort) path: PRF + lax.sort + merge +
    in-graph id recovery.  Returns (B,2P) (sel, cand_hi, cand_lo)."""
    def one(rh, rl, rn, sh, sl, sn, sd):
        p = rh.shape[0]
        r_kh, r_kl = pack_keys(*prf_tags(rh, rl, sd, impl=impl), 1)
        s_kh, s_kl = pack_keys(*prf_tags(sh, sl, sd, impl=impl), 0)
        r_kh, r_kl = _mask_pad(r_kh, r_kl, rn, PAD_A)
        s_kh, s_kl = _mask_pad(s_kh, s_kl, sn, PAD_B)
        perm = jnp.arange(p, dtype=jnp.int32)
        r_kh, r_kl, perm = lax.sort((r_kh, r_kl, perm), num_keys=2)
        s_kh, s_kl = lax.sort((s_kh, s_kl), num_keys=2)
        sel, rank, _, _ = sorted_intersect(r_kh, r_kl, s_kh, s_kl,
                                           impl=impl)
        by_tag = jnp.clip(rank - 1, 0, p - 1)
        src = jnp.take(perm, by_tag)          # merged slot -> receiver row
        return sel, jnp.take(rh, src), jnp.take(rl, src)
    return jax.vmap(one)(r_hi, r_lo, r_n, s_hi, s_lo, s_n, seeds)


_DISPATCH_BODY = {"prf": _prf_batch, "merge": _merge_batch,
                  "single": _oprf_single, "union": _union_batch}


def dispatch_key(options: AlignOptions) -> Tuple[AlignOptions, int]:
    """Canonicalize an ``AlignOptions`` into the ``_dispatch`` cache key
    plus the mesh-axis shard count.

    Only the engine-relevant fields survive (impl + resolved mesh/axis);
    protocol/backend/overlap/sort are reset to defaults so two configs
    that lower to the same executable share one cache entry.  The key is
    the frozen (hashable) config object itself — no hand-flattened
    (impl, mesh, axis) tuple to drift from the config schema."""
    mesh, axis, n_shards = resolve_batch_mesh(options.mesh,
                                              options.shard_axis)
    return AlignOptions(impl=options.impl, mesh=mesh,
                        shard_axis=axis), n_shards


@functools.lru_cache(maxsize=32)
def _dispatch(kind: str, key: AlignOptions):
    """Jitted executable for one dispatch kind, optionally shard_mapped
    so the pair batch splits over a mesh axis.  Cached per
    (kind, canonical AlignOptions) — see ``dispatch_key`` — so
    re-wrapping never re-jits; bounded (and clearable via
    ``clear_dispatch_cache``) because the mesh-keyed entries would
    otherwise pin Mesh objects and their executables for process
    lifetime."""
    fn = functools.partial(_DISPATCH_BODY[kind], impl=key.impl)
    if key.mesh is not None:
        fn = batch_shard_map(fn, key.mesh, key.shard_axis)
    return jax.jit(fn)


def clear_dispatch_cache() -> None:
    """Drop every cached dispatch executable and the warm-up record.
    Tests that build transient meshes call this so the engine's cache
    keys don't keep device meshes alive; the paired training-side hook
    is ``repro.train.vfl.clear_program_caches``."""
    _dispatch.cache_clear()
    _warm_cache.clear()


# ----------------------------------------------------- compile warm-up

_warm_cache: set = set()


def _warm(kind: str, b: int, p: int, key: AlignOptions) -> None:
    """Compile a (dispatch, pairs, P, canonical options) bucket outside
    the timed region: jit keys on shapes/dtypes only, so a zeros-input
    call builds the executable the subsequent timed call reuses."""
    wkey = (kind, b, p, key)
    if wkey in _warm_cache:
        return
    fn = _dispatch(kind, key)
    z = np.zeros((b, p), np.uint32)
    n = np.zeros((b,), np.int32)
    seeds = np.zeros((b, 2), np.uint32)
    if kind == "prf":
        out = fn(z, z, z, z, seeds)
    elif kind in ("merge", "union"):
        out = fn(z, z, z, z)
    else:
        out = fn(z, z, n, z, z, n, seeds)
    jax.block_until_ready(out)
    _warm_cache.add(wkey)


# --------------------------------------------------------- round executors

def _host_sorted_merge(r_tags64: Sequence[np.ndarray],
                       receiver_ids: Sequence[np.ndarray],
                       s_tags64: Sequence[np.ndarray], p: int,
                       key: AlignOptions,
                       n_shards: int = 1) -> List[np.ndarray]:
    """Host-sort path shared by oprf_round and match_round: numpy-sort
    each pair's u64 tags, pack the padded key-lane batch, run the merge
    dispatch, and recover ids from (sel, rank)."""
    b = len(r_tags64)
    with span("align.pack", pairs=b, p=p):
        a_kh = np.empty((b, p), np.uint32)
        a_kl = np.empty((b, p), np.uint32)
        b_kh = np.empty((b, p), np.uint32)
        b_kl = np.empty((b, p), np.uint32)
        ids_by_tag: List[np.ndarray] = []
        with span("align.host_sort", pairs=b, p=p):
            for i in range(b):
                order = np.argsort(r_tags64[i])
                ids_by_tag.append(
                    np.asarray(receiver_ids[i], np.int64)[order])
                a_kh[i], a_kl[i] = _host_key_rows(r_tags64[i][order], 1,
                                                  PAD_A, p)
                b_kh[i], b_kl[i] = _host_key_rows(np.sort(s_tags64[i]), 0,
                                                  PAD_B, p)
        args, _ = pad_batch_rows((a_kh, a_kl, b_kh, b_kl), n_shards)
    with span("align.dispatch", kind="merge", pairs=b, p=p,
              shards=n_shards,
              **_batch_counts((r_tags64, s_tags64), len(args[0]), p)):
        sel_rank = jax.block_until_ready(
            _dispatch("merge", key)(*args))
    with span("align.recover", pairs=b):
        sel = np.asarray(sel_rank[0])[:b].astype(bool)
        rank = np.asarray(sel_rank[1])[:b]
        return [np.sort(ids_by_tag[i][rank[i][sel[i]] - 1])
                for i in range(b)]


def oprf_round(sender_sets: Sequence[np.ndarray],
               receiver_sets: Sequence[np.ndarray],
               seeds: Sequence[Tuple[int, int]], *,
               options: Optional[AlignOptions] = None,
               **legacy) -> EngineRound:
    """One MPSI round of OPRF-flavor pairs, batched.

    ``seeds[i]`` is the pair's session key as two u32 words (the wire
    protocol still models the OT-extension seed agreement; see tpsi).
    Each receiver learns intersection(sender_sets[i], receiver_sets[i]).
    ``options`` (``repro.config.AlignOptions``) carries impl/sort/mesh:
    with ``options.mesh``, the pair batch shards over one mesh axis
    (module docstring) — intersections are byte-identical either way.
    Legacy ``impl=``/``sort=``/``mesh=``/``shard_axis=`` kwargs coerce
    through the shared deprecation shim.
    """
    (options,) = _coerce_options(
        "oprf_round", legacy, ("options", AlignOptions, options,
                               ALIGN_ALIASES))
    b = len(sender_sets)
    if b == 0:
        return EngineRound([], 0.0, 0)
    sort = _default_sort(options.sort)
    key, n_shards = dispatch_key(options)
    p = next_pow2(max(max((len(s) for s in sender_sets), default=0),
                      max((len(r) for r in receiver_sets), default=0), 1))
    with span("align.pack", pairs=b, p=p):
        s_hi, s_lo, s_n = _pack(sender_sets, p)
        r_hi, r_lo, r_n = _pack(receiver_sets, p)
        seed_arr = np.asarray(seeds, np.uint32).reshape(b, 2)
        lanes = ((r_hi, r_lo, r_n, s_hi, s_lo, s_n, seed_arr)
                 if sort == "device" else (r_hi, r_lo, s_hi, s_lo, seed_arr))
        args, _ = pad_batch_rows(lanes, n_shards)
    bp = args[0].shape[0]
    counts = _batch_counts((sender_sets, receiver_sets), bp, p)

    if sort == "device":
        _warm("single", bp, p, key)
        fn = _dispatch("single", key)
        t0 = time.perf_counter()
        with span("align.dispatch", kind="single", pairs=b, p=p,
                  shards=n_shards, **counts):
            out = jax.block_until_ready(fn(*args))
        with span("align.recover", pairs=b):
            sel = np.asarray(out[0])[:b].astype(bool)
            ids = (np.asarray(out[1], np.uint64)[:b] << np.uint64(32)) \
                | np.asarray(out[2], np.uint64)[:b]
            inters = [np.sort(ids[i][sel[i]].astype(np.int64))
                      for i in range(b)]
        return EngineRound(inters, time.perf_counter() - t0, 1,
                           shards=n_shards)

    _warm("prf", bp, p, key)
    _warm("merge", bp, p, key)
    fn = _dispatch("prf", key)
    t0 = time.perf_counter()
    with span("align.dispatch", kind="prf", pairs=b, p=p,
              shards=n_shards, **counts):
        tags = jax.block_until_ready(fn(*args))
    with span("align.recover", pairs=b):
        r_th, r_tl, s_th, s_tl = (np.asarray(t) for t in tags)
        join = lambda th, tl, n: ((th[:n].astype(np.uint64)
                                   << np.uint64(32)) | tl[:n])
        r_tags = [join(r_th[i], r_tl[i], int(r_n[i])) for i in range(b)]
        s_tags = [join(s_th[i], s_tl[i], int(s_n[i])) for i in range(b)]
    inters = _host_sorted_merge(r_tags, receiver_sets, s_tags, p, key,
                                n_shards)
    return EngineRound(inters, time.perf_counter() - t0, 2,
                       shards=n_shards)


def match_round(receiver_tags: Sequence[np.ndarray],
                receiver_ids: Sequence[np.ndarray],
                sender_tags: Sequence[np.ndarray], *,
                options: Optional[AlignOptions] = None,
                **legacy) -> EngineRound:
    """One MPSI round of tag-matching pairs (RSA flavor: tags are
    host-computed truncated signatures, already in [0, 2^62)).  Tags
    originate on host, so sorting is always host-side: one merge
    dispatch total.  ``receiver_ids[i]`` may be ANY int64 payload
    aligned with ``receiver_tags[i]`` (delta-PSI encodes (id, live)
    records this way); the matched payloads come back sorted."""
    (options,) = _coerce_options(
        "match_round", legacy, ("options", AlignOptions, options,
                                ALIGN_ALIASES))
    b = len(receiver_tags)
    if b == 0:
        return EngineRound([], 0.0, 0)
    key, n_shards = dispatch_key(options)
    p = next_pow2(max(max((len(t) for t in receiver_tags), default=0),
                      max((len(t) for t in sender_tags), default=0), 1))
    _warm("merge", padded_rows(b, n_shards), p, key)
    t0 = time.perf_counter()
    r_tags = [np.asarray(t, np.int64).astype(np.uint64)
              for t in receiver_tags]
    s_tags = [np.asarray(t, np.int64).astype(np.uint64)
              for t in sender_tags]
    inters = _host_sorted_merge(r_tags, receiver_ids, s_tags, p, key,
                                n_shards)
    return EngineRound(inters, time.perf_counter() - t0, 1,
                       shards=n_shards)


def union_merge(a_tags64: np.ndarray, b_tags64: np.ndarray, *,
                options: Optional[AlignOptions] = None) -> np.ndarray:
    """Sorted union of two sorted u64 tag arrays (< 2^62) through the
    bitonic-merge kernel — the delta-PSI run-compaction primitive.

    Returns the merged FULL keys ``(tag << 1) | origin`` (origin 1 =
    side A, 0 = side B; padding stripped), so the caller can resolve
    same-tag collisions by origin — ``repro.psi.delta.TagIndex`` uses
    origin as run recency.  One batched dispatch; ``options.mesh``
    shards the (padded) row batch like every other round kind, and runs
    past ``SINGLE_PASS_MAX_P`` take the tiled multi-pass merge inside
    ``sorted_intersect`` automatically."""
    options = options or AlignOptions()
    key, n_shards = dispatch_key(options)
    p = next_pow2(max(len(a_tags64), len(b_tags64), 1))
    with span("align.pack", pairs=1, p=p):
        a_kh, a_kl = _host_key_rows(np.asarray(a_tags64, np.uint64), 1,
                                    PAD_A, p)
        b_kh, b_kl = _host_key_rows(np.asarray(b_tags64, np.uint64), 0,
                                    PAD_B, p)
        args, _ = pad_batch_rows((a_kh[None], a_kl[None], b_kh[None],
                                  b_kl[None]), n_shards)
    _warm("union", args[0].shape[0], p, key)
    with span("align.dispatch", kind="union", pairs=1, p=p,
              shards=n_shards,
              **_batch_counts(((a_tags64,), (b_tags64,)), len(args[0]), p)):
        out = jax.block_until_ready(_dispatch("union", key)(*args))
    with span("align.recover", pairs=1):
        m_kh = np.asarray(out[0])[0]
        m_kl = np.asarray(out[1])[0]
        merged = (m_kh.astype(np.uint64) << np.uint64(32)) \
            | m_kl.astype(np.uint64)
        return merged[m_kh < np.uint32(0x80000000)]
