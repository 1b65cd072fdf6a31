"""What a per-layer metric reader gets: the traced window's spans, the
reduced device trace, each job's logical sizes, compile seconds, the
configuration and the device's peaks."""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

from chipbench import peaks


@dataclasses.dataclass
class Context:
    config: dict
    device_kind: str
    spans: List[Any]            # repro.obs.trace.Span, finished
    trace: Any                  # chipbench.trace_reduce.Reduced
    jobs: List[dict]            # each job's logical sizes (jobs.*.work)
    compile_s: float            # making programs inside the window

    @property
    def n_jobs(self) -> int:
        return len(self.jobs)

    def span_seconds(self, name: str) -> float:
        return sum(s.duration for s in self.spans if s.name == name)

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.spans if s.name == name)

    def per_job(self, seconds: float) -> Optional[float]:
        return seconds / self.n_jobs if self.n_jobs else None

    def roofline(self, ops: float, nbytes: float, seconds: float
                 ) -> Optional[float]:
        """Percent of the roofline, or None where the kernel never ran."""
        if seconds <= 0 or ops <= 0:
            return None
        share, _ = peaks.roofline(ops, nbytes, seconds, self.device_kind,
                                  self.config["dtype"])
        return share

    def hbm_roofline(self, nbytes: float, seconds: float
                     ) -> Optional[float]:
        """Percent of the bytes-only roofline, or None where the kernel
        never ran."""
        if seconds <= 0 or nbytes <= 0:
            return None
        return peaks.hbm_roofline(nbytes, seconds, self.device_kind)

    def ops_peak(self) -> float:
        return peaks.ops_peak(self.device_kind, self.config["dtype"])
