#!/usr/bin/env python3
"""Cut a traced job recorded on the chip down to a small test trace.

    python3 chipbench/testdata/trim_trace.py <run.xplane.pb> <out.json.gz> [ms]

Reads the ``.xplane.pb`` of a ``--trace 1`` window with
``chipbench.trace_reduce.load_xplane``, keeps the first ``ms``
milliseconds (default 40) of its first ``bench.job`` span, every device
op event inside them and the host spans that overlap them, and writes
them as gzipped JSON (``trace_reduce.Trace.to_json``), with the window
re-spanned by one ``bench.job`` span.  Times are shifted to start at 0.
``hi_treecss_job.json.gz`` beside it is one whole hi.treecss job traced
on a TPU v5e, kept whole with ``ms`` 2000.
"""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace_reduce as tr  # noqa: E402


def trim(trace: tr.Trace, ms: float) -> tr.Trace:
    job = next(e for e in trace.host if e.name == tr.WINDOW_SPAN)
    lo, hi = job.start, min(job.end, job.start + ms * 1e6)
    devices = {d: [tr.Event(e.name, e.start - lo, e.end - lo) for e in evs
                   if e.start >= lo and e.end <= hi]
               for d, evs in trace.devices.items()}
    host = [tr.Event(tr.WINDOW_SPAN, 0.0, hi - lo)]
    host += [tr.Event(e.name, max(e.start, lo) - lo, min(e.end, hi) - lo)
             for e in trace.host
             if e.name != tr.WINDOW_SPAN and e.end > lo and e.start < hi]
    return tr.Trace(devices, host)


def main(argv) -> int:
    src, dst = argv[0], argv[1]
    ms = float(argv[2]) if len(argv) > 2 else 40.0
    small = trim(tr.load_xplane(src), ms)
    with gzip.open(dst, "wt") as f:
        json.dump(small.to_json(), f)
    print(dst, sum(map(len, small.devices.values())), "device events,",
          len(small.host), "host spans")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
