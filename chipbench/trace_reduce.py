"""From a JAX profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes; it is
read with ``jax.profiler.ProfileData`` into plain events:

- device op events: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane (one event per HLO op or Mosaic kernel that ran; the event's
  name is the instruction's HLO text, ``%kmeans_update.7 = (s32[...``,
  of which the instruction's name is kept); a loop's ``while`` op and
  the ops of its body are events of their own, nested in time;
- host spans: every event on the ``/host:CPU`` plane whose name is a
  span of the program (``repro.obs`` spans become ``TraceAnnotation``s
  under ``Tracer(jax_profiler=True)``) or of the benchmark (``bench.job``).

All times are nanoseconds on the profiler's clock, which the device and
host planes share.  The reduction:

- window: from the first ``bench.job`` span's start to the last one's end;
- busy: the union of a device's op intervals inside the window, averaged
  over the devices used;
- kernel time: the sum of the durations of a kernel's events (all
  devices), matched by name: an op event is named for its HLO
  instruction, which for a Pallas kernel is the ``name`` given to
  ``pallas_call`` with a ``.<n>`` suffix (``kmeans_assign.1``), and,
  where the call sits under autodiff, inside ``jvp_`` and ``_``
  (``jvp_splitnn_bottom_.9``); suffix and wrapper are dropped before
  matching;
- idle gaps: the stretches of the window in which device 0 runs no op,
  each labelled by the innermost host span open at the gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.job"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
HOST_PLANE = "/host:CPU"
# program spans are dotted lowercase names (pipeline.train, align.round)
SPAN_NAME = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
# the instance suffix XLA gives an HLO instruction's name
INSTANCE = re.compile(r"\.\d+$")
# an op event's name is its HLO text: "%name.N = type op(...)"
HLO_NAME = re.compile(r"^%?([^\s=]+)")
# the wrapper autodiff puts round a custom call's name
AD_WRAPPER = re.compile(r"^(?:jvp|transpose)_(.+?)_?$")
# ops that only hold other ops (a loop and its body): not ops of their own
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float           # ns
    end: float             # ns


@dataclasses.dataclass
class Trace:
    """The events the reduction reads: op events per device, host spans."""
    devices: Dict[int, List[Event]]
    host: List[Event]

    def to_json(self) -> dict:
        return {"devices": {str(d): [[e.name, e.start, e.end] for e in evs]
                            for d, evs in self.devices.items()},
                "host": [[e.name, e.start, e.end] for e in self.host]}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls({int(d): [Event(*e) for e in evs]
                    for d, evs in obj["devices"].items()},
                   [Event(*e) for e in obj["host"]])


def base_name(name: str) -> str:
    """An op event's name without its instance suffix."""
    return INSTANCE.sub("", name)


def kernel_name(name: str) -> str:
    """The kernel an op event ran: its base name out of any autodiff
    wrapper (``jvp_splitnn_bottom_.9`` -> ``splitnn_bottom``)."""
    n = base_name(name)
    m = AD_WRAPPER.match(n)
    return m.group(1) if m else n


def op_name(text: str) -> str:
    """The HLO instruction's name in an op event's name."""
    m = HLO_NAME.match(text)
    return m.group(1) if m else text


def load_xplane(path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append(Event(op_name(ev.name), float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if SPAN_NAME.match(ev.name):
                        host.append(Event(ev.name, float(ev.start_ns),
                                          float(ev.start_ns
                                                + ev.duration_ns)))
    for evs in devices.values():
        evs.sort(key=lambda e: (e.start, e.end))
    host.sort(key=lambda e: (e.start, -e.end))
    return Trace(devices, host)


# ------------------------------------------------------------ reductions


def window(host: Sequence[Event]) -> Tuple[float, float]:
    jobs = [e for e in host if e.name == WINDOW_SPAN]
    if not jobs:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(e.start for e in jobs), max(e.end for e in jobs)


def busy_intervals(events: Iterable[Event], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """The union of the events' intervals clipped to [lo, hi], merged."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(t - s for s, t in busy_intervals(events, lo, hi))


def idle_gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
              hi: float) -> List[Tuple[str, float, float]]:
    """(label, start, length) of every stretch of [lo, hi] without a
    device op; the label is the innermost host span open at its middle
    (the one that started last), or "no span"."""
    gaps, cursor = [], lo
    for s, t in busy_intervals(events, lo, hi) + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, t)
    starts = [e.start for e in host]
    out = []
    for s, t in gaps:
        mid = 0.5 * (s + t)
        label = "no span"
        for e in reversed(host[:bisect.bisect_right(starts, mid)]):
            if e.end >= mid and e.name != WINDOW_SPAN:
                label = e.name
                break
        out.append((label, s, t - s))
    return out


def kernel_ns(devices: Dict[int, List[Event]], names: Sequence[str],
              prefix: bool = False) -> float:
    def hit(n):
        n = kernel_name(n)
        return any(n.startswith(k) if prefix else n == k for k in names)
    return sum(e.end - e.start for evs in devices.values() for e in evs
               if hit(e.name))


@dataclasses.dataclass
class Reduced:
    """The numbers one traced window gives, in seconds."""
    trace: Trace
    lo: float
    hi: float
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        used = sorted(self.trace.devices)[:self.n_devices]
        if not used:
            return 0.0
        return sum(busy_ns(self.trace.devices[d], self.lo, self.hi)
                   for d in used) * 1e-9 / len(used)

    def kernel_seconds(self, names: Sequence[str], prefix: bool = False
                       ) -> float:
        used = {d: evs for d, evs in self.trace.devices.items()
                if d in sorted(self.trace.devices)[:self.n_devices]}
        inside = {d: [e for e in evs if e.start >= self.lo
                      and e.end <= self.hi] for d, evs in used.items()}
        return kernel_ns(inside, names, prefix) * 1e-9

    def top_ops(self, n: int) -> List[list]:
        """Device seconds by op, instances of one kernel summed; a loop's
        op is left out, its body's ops are counted."""
        tot: Dict[str, float] = collections.defaultdict(float)
        for d in sorted(self.trace.devices)[:self.n_devices]:
            for e in self.trace.devices[d]:
                name = kernel_name(e.name)
                if (e.start >= self.lo and e.end <= self.hi
                        and name not in CONTAINERS):
                    tot[name] += (e.end - e.start) * 1e-9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def gaps(self) -> List[Tuple[str, float, float]]:
        d0 = sorted(self.trace.devices)[0] if self.trace.devices else None
        evs = self.trace.devices.get(d0, [])
        return idle_gaps(evs, self.trace.host, self.lo, self.hi)

    def top_gaps(self, n: int) -> List[list]:
        """Idle seconds by what the host was doing, largest first."""
        tot: Dict[str, float] = collections.defaultdict(float)
        for label, _, length in self.gaps():
            tot[label] += length * 1e-9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def reduce_trace(trace: Trace, n_devices: int = 1) -> Reduced:
    lo, hi = window(trace.host)
    return Reduced(trace, lo, hi, n_devices)


def reduce_dir(directory, n_devices: int = 1) -> Reduced:
    """Reduce the one ``.xplane.pb`` under ``directory``."""
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_trace(load_xplane(found[-1]), n_devices)
