"""TreeCSS end-to-end pipeline (Fig. 1): align → coreset → weighted training.

The four framework variants of Table 2 are combinations of
  MPSI topology ∈ {star, tree(ours), path}  ×  data ∈ {ALL, CSS(ours)}:

  STARALL  = Star-MPSI + full-data SplitNN        (vanilla VFL baseline)
  TREEALL  = Tree-MPSI + full-data SplitNN
  STARCSS  = Star-MPSI + Cluster-Coreset training
  TREECSS  = Tree-MPSI + Cluster-Coreset training (the paper's framework)

``run_pipeline`` measures/simulates each stage and returns a stage-by-stage
report so benchmarks can reproduce the Table-2 time comparison.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.config import (ALIGN_ALIASES, ENGINE_ALIASES, AlignOptions,
                          EngineOptions, _coerce_options)
from repro.core.coreset import CoresetResult, cluster_coreset
from repro.core.mpsi import MPSI, MPSIStats
from repro.core.splitnn import (SplitNNConfig, TrainReport, evaluate,
                                knn_predict, train_splitnn)
from repro.data.synthetic import make_id_universe
from repro.data.vertical import VerticalPartition
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, now, span, use_tracer


@dataclasses.dataclass
class PipelineReport:
    variant: str
    mpsi: MPSIStats
    coreset: Optional[CoresetResult]
    train: TrainReport
    metric: float                  # accuracy (cls) or MSE (reg)
    align_seconds: float           # simulated protocol makespan
    coreset_seconds: float
    train_seconds: float
    n_train: int
    align_wall_seconds: float = 0.0   # measured alignment wall time
    # measured stage wall times, all read from the one obs span clock so
    # they stay comparable to trace timelines (DESIGN.md §10)
    coreset_wall_seconds: float = 0.0
    train_wall_seconds: float = 0.0
    tracer: Optional[Tracer] = dataclasses.field(default=None, repr=False)

    @property
    def total_seconds(self) -> float:
        return self.align_seconds + self.coreset_seconds + self.train_seconds

    def emit_metrics(self, registry: MetricsRegistry) -> None:
        """Emit every stage's numbers into ``registry`` — the single
        snapshot the benchmarks and the CI contract gate read
        (DESIGN.md §10).  Namespaces: ``align.*`` (MPSIStats),
        ``train.*`` (EngineStats + TrainReport scalars), ``coreset.*``,
        ``pipeline.*`` (stage wall/simulated times, metric, n_train)."""
        self.mpsi.emit(registry, "align.")
        if self.train.engine_stats is not None:
            self.train.engine_stats.emit(registry, "train.")
        registry.counter("train.epochs").inc(self.train.epochs)
        registry.counter("train.steps").inc(self.train.steps)
        registry.counter("train.comm_bytes").inc(self.train.comm_bytes)
        registry.gauge("train.train_seconds").set(self.train.train_seconds)
        registry.gauge("train.simulated_comm_seconds").set(
            self.train.simulated_comm_seconds)
        if self.coreset is not None:
            registry.counter("coreset.n_coreset").inc(
                int(self.coreset.indices.shape[0]))
            registry.counter("coreset.n_groups").inc(self.coreset.n_groups)
            registry.counter("coreset.comm_bytes").inc(
                self.coreset.comm_bytes)
        registry.gauge("pipeline.metric").set(self.metric)
        registry.counter("pipeline.n_train").inc(self.n_train)
        registry.gauge("pipeline.align_seconds").set(self.align_seconds)
        registry.gauge("pipeline.coreset_seconds").set(self.coreset_seconds)
        registry.gauge("pipeline.train_seconds").set(self.train_seconds)
        registry.gauge("pipeline.align_wall_seconds").set(
            self.align_wall_seconds)
        registry.gauge("pipeline.coreset_wall_seconds").set(
            self.coreset_wall_seconds)
        registry.gauge("pipeline.train_wall_seconds").set(
            self.train_wall_seconds)


def _align(partition: VerticalPartition, topology: str, *,
           align: AlignOptions, seed: int
           ) -> Tuple[VerticalPartition, MPSIStats, float, float]:
    """Run MPSI over per-client ID sets and restrict data to the aligned set.

    Each client's ID list covers the same underlying rows; ``overlap`` of
    them are common (the paper's 70% synthetic setting maps row-indices to
    IDs so alignment has real work to do).

    Row ↔ id map: row i of the partition carries id ``sets[0][i]`` — the
    label owner's local ordering, which ``make_id_universe`` shuffles, so
    aligned ids are scattered through the row space (NOT a prefix).  The
    aligned partition is exactly the rows whose ids the MPSI
    intersection returned, in ascending row order.

    Returns (aligned, stats, simulated_seconds, wall_seconds): the
    simulated makespan drives the paper's cost model; the measured wall
    time is what the host/device backends actually spent, so end-to-end
    engine speedups are visible in ``PipelineReport``."""
    n = partition.n_samples
    m = partition.n_clients
    with span("align.ids", parties=m, rows=n):
        sets, _core = make_id_universe(m, n, align.overlap, seed=seed)
    sp = span("align.mpsi", topology=topology, protocol=align.protocol,
              backend=align.psi_backend, n_clients=m, n_ids=n)
    t0 = now()
    with sp:
        stats = MPSI[topology](sets, options=align)
    align_wall = now() - t0
    sp.set(comm_bytes=stats.total_bytes, rounds=stats.rounds,
           n_align=int(stats.intersection.shape[0]))
    inter = stats.intersection
    # id -> row: invert the label owner's id list (ids are unique, and
    # inter ⊆ sets[0] because it intersects every client's set)
    row_ids = np.asarray(sets[0], np.int64)
    order = np.argsort(row_ids)
    pos = np.searchsorted(row_ids, inter, sorter=order)
    rows = np.sort(order[pos])
    aligned = partition.take(rows)
    return aligned, stats, stats.simulated_seconds, align_wall


def run_pipeline(train_part: VerticalPartition,
                 test_part: VerticalPartition,
                 cfg: SplitNNConfig, *,
                 variant: str = "treecss",
                 clusters_per_client: int = 12,
                 use_weights: bool = True,
                 kmeans_impl: str = "ref",
                 seed: int = 0,
                 knn_k: int = 5,
                 options: Optional[EngineOptions] = None,
                 align: Optional[AlignOptions] = None,
                 **legacy) -> PipelineReport:
    """Engine knobs live on ``options=EngineOptions(...)``, alignment
    knobs on ``align=AlignOptions(...)`` (``repro.config``; DESIGN.md
    §13) — the 17-kwarg legacy surface still works through
    ``_coerce_options`` (one ``DeprecationWarning``, bitwise-identical
    results; property-tested in tests/test_config.py).

    ``options.mesh`` (with optional ``shard_axis``) shards ALL THREE
    device-path stages through one knob, and accepts 1-D ``("data",)``
    or 2-D ``(data, model)`` meshes (``launch.mesh.make_train_mesh``):
    the PSI engine's per-round pair batch (``align.psi_backend=
    "device"``; the alignment stage inherits the engine mesh via
    ``AlignOptions.with_engine_defaults`` unless ``align.mesh`` is set)
    and the CSS batched client fit shard over ``data`` (replicating
    over ``model`` — byte-identical to single-device either way), and
    the SplitNN scan engine shards its per-step batch axis over
    ``data`` plus, on a 2-D mesh, the M-client bottom axis over
    ``model`` (the client→server activation send lowers to one
    all-gather; DESIGN.md §8) — training matches single-device within
    gemm/psum-reassociation ulps (DESIGN.md §5, §7).
    ``options.train_engine``/``bottom_impl`` select the training engine
    and the block-diagonal bottom implementation ("pallas" = the fused
    VMEM-resident kernel on real TPU); ``fuse_gather``/``block_b``
    thread through to ``train_splitnn`` (the scalar-prefetch
    schedule-gather toggle and the bottom kernel's batch tile).
    Evaluation reuses ``block_b`` and, for the slab impls,
    ``bottom_impl`` through the batched scoring path.
    ``options.quant`` ("int8"|"fp8", DESIGN.md §12) quantizes the
    training stage's per-step activation send (int8 also runs the int8
    bottom kernels); evaluation applies the same wire rounding, so the
    metric reflects quantized inference of the quantized-trained model.

    ``options.trace`` turns on the observability layer (DESIGN.md §10):
    pass a ``repro.obs.Tracer`` to collect this run's spans into it
    (sharing one tracer across calls builds a single timeline), or any
    truthy value to self-create one — either way the tracer comes back
    on ``PipelineReport.tracer`` for Chrome-trace export.  Tracing only
    brackets host code already on the execution path, so engine
    counters (dispatches/host syncs) are unchanged by it."""
    options, align = _coerce_options(
        "run_pipeline", legacy,
        ("options", EngineOptions, options, ENGINE_ALIASES),
        ("align", AlignOptions, align, ALIGN_ALIASES))
    align = align.with_engine_defaults(options)
    variant = variant.lower()
    topology = "tree" if variant.startswith("tree") else (
        "path" if variant.startswith("path") else "star")
    use_css = variant.endswith("css")
    trace = options.trace
    tracer = trace if isinstance(trace, Tracer) else (
        Tracer() if trace else None)

    with use_tracer(tracer), span("pipeline.run", variant=variant,
                                  model=cfg.model, seed=seed):
        with span("pipeline.align", topology=topology,
                  protocol=align.protocol, backend=align.psi_backend):
            aligned, mpsi_stats, align_secs, align_wall = _align(
                train_part, topology, align=align, seed=seed)

        coreset_res = None
        weights = None
        coreset_wall = 0.0
        if use_css:
            from repro.core.coreset import clients_batchable
            if not clients_batchable(aligned.client_features,
                                     clusters=clusters_per_client):
                # sequential path: warm the kmeans jit cache on the exact
                # shapes so stage timing compares protocols, not XLA
                # compilation (the batched path AOT-compiles internally)
                for f in aligned.client_features:
                    from repro.core.kmeans import kmeans as _km
                    _km(f, min(clusters_per_client, f.shape[0]), seed=seed,
                        impl=kmeans_impl)
            cs_sp = span("pipeline.coreset", k=clusters_per_client,
                         rows=aligned.n_samples)
            t0 = now()
            with cs_sp:
                coreset_res = cluster_coreset(
                    aligned, clusters_per_client, seed=seed,
                    kmeans_impl=kmeans_impl, mesh=options.mesh,
                    shard_axis=options.shard_axis)
            coreset_wall = now() - t0
            cs_sp.set(n_coreset=int(coreset_res.indices.shape[0]),
                      comm_bytes=coreset_res.comm_bytes)
            train_data = aligned.take(coreset_res.indices)
            if use_weights:
                weights = coreset_res.weights
            # steps 1-2 run concurrently on the clients: stage cost is the
            # per-client makespan + label-owner selection (+ HE)
            coreset_secs = coreset_res.makespan_seconds
        else:
            train_data = aligned
            coreset_secs = 0.0

        if cfg.model == "knn":
            t0 = now()
            with span("pipeline.train", model="knn",
                      rows=train_data.n_samples):
                pred = knn_predict(train_data, test_part, knn_k,
                                   sample_weights=weights)
            train_secs = now() - t0
            train_wall = train_secs
            metric = float(np.mean(pred == test_part.labels))
            train_report = TrainReport(losses=[], epochs=0, steps=0,
                                       train_seconds=train_secs,
                                       comm_bytes=0,
                                       simulated_comm_seconds=0.0,
                                       params=None)
        else:
            tr_sp = span("pipeline.train", model=cfg.model,
                         engine=options.train_engine,
                         rows=train_data.n_samples)
            t0 = now()
            with tr_sp:
                train_report = train_splitnn(
                    train_data, cfg, sample_weights=weights,
                    options=options)
            train_wall = now() - t0
            tr_sp.set(comm_bytes=train_report.comm_bytes,
                      epochs=train_report.epochs)
            train_secs = (train_report.train_seconds
                          + train_report.simulated_comm_seconds)
            eval_impl = (options.bottom_impl
                         if options.bottom_impl in ("ref", "pallas")
                         else "ref")
            with span("pipeline.serve", rows=test_part.n_samples):
                metric = evaluate(train_report.params, cfg, test_part,
                                  block_b=options.block_b,
                                  bottom_impl=eval_impl,
                                  quant=options.quant)

    return PipelineReport(
        variant=variant, mpsi=mpsi_stats, coreset=coreset_res,
        train=train_report, metric=metric, align_seconds=align_secs,
        coreset_seconds=coreset_secs, train_seconds=train_secs,
        n_train=train_data.n_samples, align_wall_seconds=align_wall,
        coreset_wall_seconds=coreset_wall, train_wall_seconds=train_wall,
        tracer=tracer)
