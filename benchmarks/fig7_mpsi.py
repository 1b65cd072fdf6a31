"""Fig. 7 — Tree-MPSI vs Path/Star MPSI, RSA- and OT-based TPSI, plus the
volume-aware scheduling ablation (client i holds i×base samples) — plus
the PSI engine microbenchmark (per-element host OPRF loop vs the
vectorized device tag-eval + sorted-intersect path) at N up to 10⁶.

Paper claims: avg ≈2.25× speedup for Tree over Path/Star with 10 clients,
growing with dataset size; scheduling gains grow with client count.
"""
from __future__ import annotations

import hashlib
import time

import numpy as np

from benchmarks.common import emit, fmt
from repro.core.mpsi import MPSI
from repro.data.synthetic import make_id_universe

N_CLIENTS = 10


def run(quick: bool = True):
    sizes_rsa = [500, 1000, 2000] if quick else [2000, 5000, 10000]
    sizes_oprf = [5000, 20000, 50000] if quick else [20000, 100000, 500000]

    rows = []
    for proto, sizes in (("rsa", sizes_rsa), ("oprf", sizes_oprf)):
        for n in sizes:
            sets, core = make_id_universe(N_CLIENTS, n, 0.7, seed=n)
            times = {}
            for topo in ("tree", "path", "star"):
                t0 = time.perf_counter()
                res = MPSI[topo](sets, protocol=proto, use_he=False)
                wall = time.perf_counter() - t0
                assert len(res.intersection) == len(core)
                times[topo] = res.simulated_seconds
                rows.append(dict(
                    fig="7a" if proto == "rsa" else "7b", protocol=proto,
                    topology=topo, n_per_client=n, rounds=res.rounds,
                    sim_seconds=fmt(res.simulated_seconds),
                    mbytes=fmt(res.total_bytes / 1e6),
                    wall_seconds=fmt(wall)))
            rows.append(dict(
                fig="7-speedup", protocol=proto, topology="tree-vs-path",
                n_per_client=n, rounds="",
                sim_seconds=fmt(times["path"] / times["tree"], 2),
                mbytes="", wall_seconds=""))
            rows.append(dict(
                fig="7-speedup", protocol=proto, topology="tree-vs-star",
                n_per_client=n, rounds="",
                sim_seconds=fmt(times["star"] / times["tree"], 2),
                mbytes="", wall_seconds=""))
    emit(rows, "fig7ab_mpsi")

    # --- Fig 7(c): volume-aware scheduling, client i holds base×(i+1)
    rows = []
    base = 300 if quick else 1000
    for m in (4, 6, 8, 10):
        sizes = [base * (i + 1) for i in range(m)]
        sets, core = make_id_universe(m, sizes, 0.7, seed=m)
        r_opt = MPSI["tree"](sets, protocol="rsa", volume_aware=True,
                             use_he=False)
        r_base = MPSI["tree"](sets, protocol="rsa", volume_aware=False,
                              use_he=False)
        assert len(r_opt.intersection) == len(core)
        rows.append(dict(
            n_clients=m, base=base,
            opt_seconds=fmt(r_opt.simulated_seconds),
            base_seconds=fmt(r_base.simulated_seconds),
            speedup=fmt(r_base.simulated_seconds / r_opt.simulated_seconds,
                        2),
            opt_mbytes=fmt(r_opt.total_bytes / 1e6),
            base_mbytes=fmt(r_base.total_bytes / 1e6)))
    emit(rows, "fig7c_scheduling")
    run_psi_engine_perf(quick=quick)
    run_psi_shard_sweep(quick=quick)


# ---------------------------------------------------------- PSI engine

def _host_tag_intersect(sender: np.ndarray, receiver: np.ndarray,
                        seed_bytes: bytes) -> np.ndarray:
    """The seed path tpsi_oprf ran per pair: one sha256 per element plus
    dict matching — pure interpreter throughput, the engine's baseline."""
    h = lambda x: hashlib.sha256(
        seed_bytes + int(x).to_bytes(8, "little")).digest()
    recv_tags = {h(y): int(y) for y in receiver}
    return np.asarray(sorted(recv_tags[t] for t in map(h, sender)
                             if t in recv_tags), np.int64)


def run_psi_engine_perf(quick: bool = True, sizes=None):
    """Host-vs-device alignment engine: tag-eval + intersect throughput
    for one TPSI pair at |send| = |recv| = N, 50% overlap.

    Variants: the per-element host loop (seed baseline), the vectorized
    jnp ref path (PRF + lax.sort + bitonic merge — the algorithmic win,
    meaningful on CPU), and the Pallas kernel path (meaningful on a TPU;
    on a CPU backend the kernels run in interpret mode and its
    wall-clock is emulator overhead, as in fig6's kmeans engine rows).
    """
    from repro.kernels.padding import interpret
    from repro.kernels.sorted_intersect.kernel import SINGLE_PASS_MAX_P
    from repro.kernels.sorted_intersect.ops import next_pow2
    from repro.psi import engine as psi_engine

    sizes = sizes or ([20_000, 100_000] if quick else
                      [100_000, 300_000, 1_000_000])
    variants = [("host-loop", None), ("engine-ref", "ref")]
    if not interpret() or quick:
        variants.append(("engine-pallas", "pallas"))
    rows = []
    rng = np.random.default_rng(0)
    for n in sizes:
        universe = rng.choice(3 * n, size=int(1.5 * n), replace=False)
        sender = np.sort(universe[:n]).astype(np.int64)
        receiver = np.sort(universe[n // 2:n // 2 + n]).astype(np.int64)
        expect = np.intersect1d(sender, receiver)
        base = None
        for name, impl in variants:
            if impl is None:
                t0 = time.perf_counter()
                got = _host_tag_intersect(sender, receiver, b"\x07" * 32)
                secs = time.perf_counter() - t0
            else:
                eng = lambda: psi_engine.oprf_round(
                    [sender], [receiver], [(7, 11)], impl=impl)
                eng()                       # compile + warm the jit cache
                secs, got = np.inf, None    # best-of-3: 1-core noise
                for _ in range(3):
                    t0 = time.perf_counter()
                    got = eng().intersections[0]
                    secs = min(secs, time.perf_counter() - t0)
            assert np.array_equal(got, expect), name
            base = base if base is not None else secs
            # past the single-pass VMEM bound, impl="pallas" rows
            # measure the multi-pass tiled merge schedule — flag them
            tiled = (impl == "pallas"
                     and next_pow2(n) > SINGLE_PASS_MAX_P)
            rows.append(dict(
                n=n, variant=name, matched=len(expect),
                seconds=fmt(secs, 4),
                melem_per_s=fmt(2 * n / secs / 1e6, 2),
                speedup_vs_host=fmt(base / secs, 2),
                pallas_interpret=int(interpret()),
                merge_tiled=int(tiled)))
    emit(rows, "fig7_psi_engine")


def run_psi_shard_sweep(quick: bool = True, sizes=None):
    """Device-count sweep of the sharded MPSI round (DESIGN.md §5): one
    8-pair OPRF round batched through the engine with its pair batch
    shard_mapped over 1..D devices.  On virtual CPU devices
    (``--xla_force_host_platform_device_count=8``, the CI job) the
    wall-clock mostly proves the path runs and stays byte-identical;
    speedups become meaningful on real multi-chip hardware.
    """
    import jax

    from repro.launch.mesh import make_data_mesh
    from repro.psi import engine as psi_engine

    sizes = sizes or ([20_000] if quick else [100_000, 500_000])
    n_dev = len(jax.devices())
    counts = [c for c in (1, 2, 4, 8, 16) if c <= n_dev]
    rng = np.random.default_rng(0)
    rows = []
    for n in sizes:
        senders, receivers, seeds, baseline = [], [], [], None
        for i in range(8):
            universe = rng.choice(3 * n, size=int(1.5 * n), replace=False)
            senders.append(np.sort(universe[:n]).astype(np.int64))
            receivers.append(np.sort(
                universe[n // 2:n // 2 + n]).astype(np.int64))
            seeds.append((int(rng.integers(0, 2**32)),
                          int(rng.integers(0, 2**32))))
        for c in counts:
            mesh = None if c == 1 else make_data_mesh(c)
            eng = lambda: psi_engine.oprf_round(
                senders, receivers, seeds, impl="pallas", sort="host",
                mesh=mesh)
            eng()                      # compile + warm the jit cache
            secs, rnd = np.inf, None
            for _ in range(3):
                t0 = time.perf_counter()
                rnd = eng()
                secs = min(secs, time.perf_counter() - t0)
            if baseline is None:
                baseline = rnd.intersections
            assert all(np.array_equal(a, b) for a, b in
                       zip(rnd.intersections, baseline)), c
            rows.append(dict(
                n_per_pair=n, pairs=8, devices=c, shards=rnd.shards,
                seconds=fmt(secs, 4),
                melem_per_s=fmt(16 * n / secs / 1e6, 2),
                parity_vs_1dev=1))
    emit(rows, "fig7_psi_shard")


if __name__ == "__main__":
    run()
