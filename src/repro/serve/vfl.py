"""Continuous-batching VFL scoring engine (DESIGN.md §9).

The prediction side of the system: aligned clients stream feature rows
as *requests* (one request = one user's batch of aligned rows, each row
split into the M clients' feature slices), and the engine scores them
through the SAME packed-slab bottom path the trainer uses —
``pack_slab_params`` + the ``splitnn_bottom`` kernel via
``train.vfl.make_score_step`` — so serving and training share one
parameter layout and one compiled forward.

Instead of blocking until a full device batch forms (the historical
``splitnn.predict`` shape: the WHOLE partition in one dispatch), a
slot-based scheduler (modeled on MaxText-style prefill/decode slot
management) admits requests into a fixed-shape ``(M, slots, d_max)``
device batch:

- every dispatch has the same shape — one compile, ever — with empty
  slots simply carrying don't-care rows whose outputs are discarded
  (row independence of the forward makes this exact: an occupied slot's
  output is bitwise-identical at any occupancy);
- admission is FIFO **with backfill**: a request whose remaining rows
  fit the free slots is admitted whole (its outputs return from one
  dispatch); one that does not fit is deferred and LATER, SMALLER
  requests may jump in to fill the batch — so completion is genuinely
  out of order and head-of-line blocking does not empty the batch;
- starvation is bounded: after ``max_defer`` deferrals a request splits
  across dispatches anyway (``stats.forced_splits``), and oversized
  requests (rows > slots) always stream greedily;
- ``ServeStats`` counts dispatches, admitted rows, padded (empty)
  slot-steps and summed occupancy, so the CI counter contract can gate
  the scheduler exactly like the train engine's dispatch/sync contract.

``score_partition`` is the offline/eval flavor, which
``splitnn.predict``/``evaluate`` route through: the whole partition goes
to the device in one put, one program maps the fixed ``block_b``-row
batches over it (pad-and-truncate remainder) and the outputs come back
in one read.  Device memory is bounded by ``SCORE_DEVICE_BUDGET`` (a
larger partition goes in chunks of equal block count), and the result
is bitwise-equal to the one-shot ``splitnn_forward`` path.

``simulate_trace`` drives an engine over an open-loop arrival trace on
a virtual clock (fixed or measured per-dispatch service time) under two
policies — ``"continuous"`` (work-conserving: dispatch whatever is
admitted) and ``"blocking"`` (wait for a full batch; flush at end of
stream) — which is how ``benchmarks/serve_vfl.py`` produces the
p50/p99-vs-offered-load curves deterministically.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.kernels.padding import round_up
from repro.obs.metrics import Histogram, StatsMixin
from repro.obs.trace import span
from repro.train.vfl import make_partition_score, make_score_step

__all__ = [
    "ServeStats", "ScoreRequest", "VFLScoringEngine", "SimReport",
    "score_partition", "simulate_trace",
]


# ------------------------------------------------------------------ stats


@dataclasses.dataclass
class ServeStats(StatsMixin):
    """Measured execution counts for one scoring engine (the serving
    analogue of ``train.vfl.EngineStats``; every field is a
    deterministic function of the request trace + scheduler knobs, so
    the CI contract can pin them).

    ``padded_slots`` counts empty slot-steps (slots × dispatches minus
    occupied), ``occupancy_sum`` the occupied slots summed over
    dispatches — ``mean_occupancy`` is the batch-utilization figure of
    merit for continuous batching.

    ``CONTRACT_FIELDS`` (via ``repro.obs.StatsMixin``, DESIGN.md §10)
    is the exact counter list ``engine_contract.json`` pins per smoke
    row — declared here so the gate and the benchmark can never drift."""
    dispatches: int = 0
    admitted_rows: int = 0
    padded_slots: int = 0
    occupancy_sum: int = 0
    requests: int = 0
    completed: int = 0
    forced_splits: int = 0
    slots: int = 0
    bottom_impl: str = "ref"
    quant: str = "none"
    # delta-PSI streaming (DESIGN.md §13): rows dropped at submission
    # because their ids left the aligned set, and aligned-set updates
    # received.  Deliberately NOT in CONTRACT_FIELDS — the pinned smoke
    # rows predate eligibility filtering and must stay byte-stable.
    rejected_rows: int = 0
    eligible_updates: int = 0

    CONTRACT_FIELDS = ("dispatches", "admitted_rows", "padded_slots",
                       "occupancy_sum", "completed", "forced_splits")

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.dispatches if self.dispatches else 0.0


@dataclasses.dataclass
class ScoreRequest:
    """One scoring request: ``features`` holds the M clients' aligned
    slices, each ``(rows, d_m)`` (or ``(d_m,)`` for a single row).
    ``arrival`` is the open-loop arrival time in virtual seconds —
    only ``simulate_trace`` reads it."""
    rid: int
    features: List[np.ndarray]
    arrival: float = 0.0


class _Pending:
    """Scheduler-internal per-request state: the request's rows packed
    into one (M, rows, d_max) block, the next row to admit, and the
    output buffer rows scatter into as their dispatches retire."""
    __slots__ = ("rid", "block", "n_rows", "next_row", "done", "out",
                 "deferrals")

    def __init__(self, rid: int, block: np.ndarray):
        self.rid = rid
        self.block = block
        self.n_rows = block.shape[1]
        self.next_row = 0
        self.done = 0
        self.out: Optional[np.ndarray] = None
        self.deferrals = 0


class VFLScoringEngine:
    """Slot-based continuous-batching scorer for a trained SplitNN.

    ``params`` is model-zoo form (``TrainReport.params`` — the handoff
    re-packs it through ``pack_slab_params``); ``slots`` is the fixed
    device batch size.  Drive it with ``submit`` + ``step`` (one
    admission + dispatch round, returning the requests that completed),
    or ``score_requests`` to run a list to completion.
    """

    def __init__(self, params, cfg, feature_dims: Optional[Sequence[int]]
                 = None, *, slots: int = 64, bottom_impl: str = "ref",
                 block_b: Optional[int] = None, max_defer: int = 2,
                 quant: Optional[str] = None):
        if feature_dims is None:
            feature_dims = [bp["w"].shape[0] for bp in params["bottoms"]]
        self.cfg = cfg
        self.feature_dims = [int(d) for d in feature_dims]
        self.m = len(self.feature_dims)
        self.d_max = max(self.feature_dims)
        self.slots = int(slots)
        self.max_defer = int(max_defer)
        # quant routes scoring through the SAME wire rounding quantized
        # training used (fake-quantized bottom acts, DESIGN.md §12)
        self.packed, self._score = make_score_step(
            params, cfg, self.feature_dims, bottom_impl=bottom_impl,
            block_b=int(block_b or slots), quant=quant)
        self.stats = ServeStats(slots=self.slots, bottom_impl=bottom_impl,
                                quant=quant or "none")
        self._xbuf = np.zeros((self.m, self.slots, self.d_max), np.float32)
        self._slot_req: List[Optional[_Pending]] = [None] * self.slots
        self._slot_row = np.zeros(self.slots, np.int64)
        self._queue: "collections.deque[_Pending]" = collections.deque()
        # None = no eligibility filter (every row scores); otherwise a
        # sorted id array maintained by the delta-PSI stream
        self._eligible: Optional[np.ndarray] = None

    @classmethod
    def from_report(cls, report, cfg, **kw) -> "VFLScoringEngine":
        """Engine straight off a ``TrainReport`` (the train→serve
        slab-params handoff)."""
        return cls(report.params, cfg, **kw)

    # ------------------------------------------------------------ state

    @property
    def free_slots(self) -> int:
        return sum(r is None for r in self._slot_req)

    @property
    def occupied_slots(self) -> int:
        return self.slots - self.free_slots

    @property
    def queued_rows(self) -> int:
        return sum(r.n_rows - r.next_row for r in self._queue)

    @property
    def has_work(self) -> bool:
        return self.occupied_slots > 0 or len(self._queue) > 0

    # ----------------------------------------------------- eligibility

    def set_eligible(self, ids: Optional[Sequence[int]]) -> None:
        """Install (or with ``None`` clear) the eligible-id filter —
        rows submitted with ``row_ids`` outside it are rejected.  The
        delta-PSI coordinator seeds this with the live aligned set
        (``DeltaMPSI.stream_into``)."""
        self._eligible = (None if ids is None
                          else np.unique(np.asarray(ids, np.int64)))
        self.stats.eligible_updates += 1

    def apply_aligned_delta(self, added: Sequence[int],
                            removed: Sequence[int]) -> None:
        """Patch the eligible set with one aligned-set delta (the
        ``AlignedDelta`` stream from ``repro.psi.delta``) — no pipeline
        restart, queued/in-flight rows are unaffected."""
        cur = (self._eligible if self._eligible is not None
               else np.empty(0, np.int64))
        cur = np.setdiff1d(cur, np.asarray(removed, np.int64))
        self._eligible = np.union1d(cur, np.asarray(added, np.int64))
        self.stats.eligible_updates += 1

    # ------------------------------------------------------- submission

    def submit(self, rid: int, features: Sequence[np.ndarray],
               row_ids: Optional[Sequence[int]] = None) -> int:
        """Enqueue one request: ``features`` is the M clients' aligned
        slices for this user, each (rows, d_m) — or (d_m,) vectors for a
        single row.  ``row_ids`` (one aligned id per row) lets the
        eligibility filter drop rows whose ids have left the aligned
        set (``stats.rejected_rows``); a request with no eligible rows
        is not enqueued.  Returns the number of rows enqueued."""
        feats = [np.atleast_2d(np.asarray(f, np.float32)) for f in features]
        if len(feats) != self.m:
            raise ValueError(f"expected {self.m} client slices, "
                             f"got {len(feats)}")
        rows = feats[0].shape[0]
        for f, d in zip(feats, self.feature_dims):
            if f.shape != (rows, d):
                raise ValueError(f"client slice {f.shape} != ({rows}, {d})")
        if row_ids is not None and self._eligible is not None:
            ids = np.asarray(row_ids, np.int64).reshape(-1)
            if ids.shape[0] != rows:
                raise ValueError(f"row_ids has {ids.shape[0]} entries "
                                 f"for {rows} rows")
            keep = np.isin(ids, self._eligible)
            self.stats.rejected_rows += int(rows - keep.sum())
            if not keep.any():
                return 0
            feats = [f[keep] for f in feats]
            rows = int(keep.sum())
        block = np.zeros((self.m, rows, self.d_max), np.float32)
        for i, f in enumerate(feats):
            block[i, :, :f.shape[1]] = f
        self._queue.append(_Pending(int(rid), block))
        self.stats.requests += 1
        return rows

    # -------------------------------------------------------- scheduler

    def admit(self) -> int:
        """Fill free slots from the queue: FIFO with backfill.

        A request is admitted whole when its remaining rows fit the free
        slots; otherwise it is deferred and later smaller requests may
        fill the batch instead.  Oversized requests (rows > slots) and
        requests deferred ``max_defer`` times split across dispatches —
        bounded wait, no starvation.  Returns the number of rows
        admitted this round."""
        free = [s for s in range(self.slots) if self._slot_req[s] is None]
        admitted = 0
        sp = span("serve.admit", queued=len(self._queue), free=len(free))
        with sp:
            admitted = self._admit_into(free)
        sp.set(admitted=admitted)
        self.stats.admitted_rows += admitted
        return admitted

    def _admit_into(self, free: List[int]) -> int:
        admitted = 0
        for req in list(self._queue):
            if not free:
                break
            rem = req.n_rows - req.next_row
            if rem > len(free):
                splittable = rem > self.slots or req.deferrals >= self.max_defer
                if not splittable:
                    req.deferrals += 1
                    continue
                if rem <= self.slots:
                    self.stats.forced_splits += 1
            take = min(rem, len(free))
            for _ in range(take):
                s = free.pop(0)
                self._slot_req[s] = req
                self._slot_row[s] = req.next_row
                self._xbuf[:, s, :] = req.block[:, req.next_row, :]
                req.next_row += 1
            admitted += take
            if req.next_row == req.n_rows:
                self._queue.remove(req)
        return admitted

    def dispatch(self) -> List[Tuple[int, np.ndarray]]:
        """Score the current batch (one fixed-shape device dispatch),
        scatter outputs back to their requests, and return the
        ``(rid, outputs)`` pairs that completed — possibly out of
        submission order."""
        occ = [s for s in range(self.slots) if self._slot_req[s] is not None]
        if not occ:
            return []
        with span("serve.dispatch", occupancy=len(occ), slots=self.slots,
                  rows=len(occ), bottom_impl=self.stats.bottom_impl):
            out = np.asarray(self._score(self.packed,
                                         jnp.asarray(self._xbuf)))
        self.stats.dispatches += 1
        self.stats.occupancy_sum += len(occ)
        self.stats.padded_slots += self.slots - len(occ)
        finished: List[_Pending] = []
        for s in occ:
            req = self._slot_req[s]
            if req.out is None:
                req.out = np.empty((req.n_rows, out.shape[1]), np.float32)
            req.out[self._slot_row[s]] = out[s]
            req.done += 1
            self._slot_req[s] = None
            if req.done == req.n_rows:
                finished.append(req)
        completed = []
        for req in finished:
            self.stats.completed += 1
            completed.append((req.rid, req.out))
        return completed

    def step(self) -> List[Tuple[int, np.ndarray]]:
        """One scheduler round: admit, then dispatch if anything is
        batched."""
        self.admit()
        return self.dispatch()

    def score_requests(self, requests: Sequence[Tuple[int, Sequence[
            np.ndarray]]]) -> Dict[int, np.ndarray]:
        """Submit every (rid, features) pair and run the engine dry.
        Convenience for tests and offline scoring."""
        for rid, feats in requests:
            self.submit(rid, feats)
        results: Dict[int, np.ndarray] = {}
        while self.has_work:
            for rid, out in self.step():
                results[rid] = out
        return results


# ------------------------------------------------------- offline scoring

# Device bytes of the partition's slab one scoring dispatch may hold,
# counted lane-padded (d_max rounded up to 128), which bounds the layout
# XLA picks: YP's 153,000 test rows over 3 parties count 235 MB and go
# in one dispatch (compiled for a v5e, its rows and padded slab take
# 118 MB); a larger partition goes in chunks of equal block count.
SCORE_DEVICE_BUDGET = 256 * 2**20


def score_partition(params, cfg, partition, *, block_b: int = 512,
                    bottom_impl: str = "ref",
                    quant: Optional[str] = None) -> np.ndarray:
    """Score a whole ``VerticalPartition`` through fixed-shape batches.

    The batched replacement for the historical one-dispatch
    ``splitnn_forward`` eval: each client's rows go to the device as
    they are, in one put, and one program pads and stacks them into a
    slab of whole ``min(block_b, N)``-row blocks (the remainder
    zero-padded and truncated — row independence makes this exact),
    maps the per-block forward over the blocks, and is read back once.
    Device memory is bounded by ``SCORE_DEVICE_BUDGET``: past it the
    blocks go in equal chunks, one dispatch each.  Each dispatch is one
    ``serve.dispatch`` span whose ``blocks`` counts its blocks.  Returns
    the raw (N, o) outputs, bitwise-equal to the one-shot forward.
    """
    fd = [f.shape[1] for f in partition.client_features]
    n = partition.n_samples
    if n == 0:
        if cfg.model in ("lr", "linreg"):
            o = params["top"]["b"].shape[0]
        else:
            o = params["top"]["w2"].shape[1]
        return np.zeros((0, o), np.float32)
    bs = min(int(block_b), n)
    n_blocks = -(-n // bs)
    block_bytes = len(fd) * bs * round_up(max(fd), 128) * 4
    n_dispatch = -(-n_blocks // max(1, SCORE_DEVICE_BUDGET // block_bytes))
    blocks = -(-n_blocks // n_dispatch)           # equal blocks per dispatch
    rows = blocks * bs
    packed, score = make_partition_score(params, cfg, fd,
                                         bottom_impl=bottom_impl,
                                         block_b=bs, quant=quant)
    outs = []
    for s in range(0, n, rows):
        real = min(rows, n - s)
        xs = [np.asarray(f[s:s + real], np.float32)
              for f in partition.client_features]
        if s and real < rows:   # short last chunk: one shape for all
            xs = [np.pad(x, ((0, rows - real), (0, 0))) for x in xs]
        with span("serve.dispatch", rows=real, slots=rows, occupancy=real,
                  blocks=blocks, bottom_impl=bottom_impl):
            outs.append(np.asarray(score(
                packed, *[jnp.asarray(x) for x in xs]))[:real])
    return np.concatenate(outs, axis=0)


# ---------------------------------------------------------- trace driver


@dataclasses.dataclass
class SimReport:
    """One policy's run over one trace: per-request virtual latency,
    final counters, total virtual makespan and measured wall time.

    ``service_hist``/``wall_hist`` are the per-dispatch service-time
    distributions (``repro.obs.Histogram``): ``service_hist`` on the
    virtual clock (what latency percentiles are built from —
    deterministic under a fixed ``service_seconds``), ``wall_hist`` the
    MEASURED wall time of every dispatch, which used to be discarded
    once totaled.  ``benchmarks/serve_vfl.py`` surfaces both as
    p50/p99-per-dispatch CSV columns."""
    policy: str
    latencies: Dict[int, float]
    results: Dict[int, np.ndarray]
    stats: ServeStats
    makespan: float
    wall_seconds: float
    service_hist: Histogram = dataclasses.field(
        default_factory=lambda: Histogram("serve.service_s"))
    wall_hist: Histogram = dataclasses.field(
        default_factory=lambda: Histogram("serve.dispatch_wall_s"))

    def percentile(self, q: float) -> float:
        return float(np.percentile(np.asarray(list(self.latencies.values())),
                                   q)) if self.latencies else 0.0


def simulate_trace(engine: VFLScoringEngine, trace: Sequence[ScoreRequest],
                   *, policy: str = "continuous",
                   service_seconds: Union[float, Callable[[int], float],
                                          None] = None) -> SimReport:
    """Drive ``engine`` over an open-loop arrival ``trace`` (sorted by
    ``arrival``) on a virtual clock.

    ``policy="continuous"`` is work-conserving: after admitting every
    arrived request, dispatch whatever is batched — partially-filled
    batches ship instead of waiting.  ``policy="blocking"`` models the
    historical full-batch path: dispatch only when all slots fill (or
    the stream has ended), so at partial load requests wait for the
    batch to form.  ``service_seconds`` is the per-dispatch cost on the
    virtual clock: a float (deterministic — what the CI smoke trace
    pins), a callable of the occupied-slot count, or ``None`` to use
    each dispatch's measured wall time.  Latency per request =
    completion time − arrival time, both virtual."""
    if policy not in ("continuous", "blocking"):
        raise ValueError(policy)
    t = 0.0
    i = 0
    n = len(trace)
    arrivals: Dict[int, float] = {}
    latencies: Dict[int, float] = {}
    results: Dict[int, np.ndarray] = {}
    service_hist = Histogram("serve.service_s")
    wall_hist = Histogram("serve.dispatch_wall_s")
    wall0 = time.perf_counter()
    while True:
        while i < n and trace[i].arrival <= t:
            engine.submit(trace[i].rid, trace[i].features)
            arrivals[trace[i].rid] = trace[i].arrival
            i += 1
        engine.admit()
        occ = engine.occupied_slots
        if occ == 0 and i >= n and len(engine._queue) == 0:
            break
        drained = i >= n
        if policy == "continuous":
            fire = occ > 0
        else:
            fire = engine.free_slots == 0 or (drained and occ > 0)
        if fire:
            w0 = time.perf_counter()
            completed = engine.dispatch()
            dt_wall = time.perf_counter() - w0
            wall_hist.observe(dt_wall)        # measured, no longer discarded
            dt = dt_wall
            if service_seconds is not None:
                dt = (service_seconds(occ) if callable(service_seconds)
                      else float(service_seconds))
            service_hist.observe(dt)          # virtual-clock service time
            t += dt
            for rid, out in completed:
                latencies[rid] = t - arrivals[rid]
                results[rid] = out
        elif i < n:
            t = max(t, trace[i].arrival)     # idle until the next arrival
        else:
            # blocking, drained, occ == 0 but deferred rows queued: the
            # next admit round will place them (all slots are free)
            continue
    return SimReport(policy=policy, latencies=latencies, results=results,
                     stats=engine.stats, makespan=t,
                     wall_seconds=time.perf_counter() - wall0,
                     service_hist=service_hist, wall_hist=wall_hist)
