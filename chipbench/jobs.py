"""What every job kind shares.

A traffic file names its ``job`` kind; the kind is the module
``kinds/<job>.py`` beside this one, with a class ``Job`` built on
``Job`` here, which ``chipbench.registry`` finds by that name.  A later
kind (serving, delta alignment) is a new file, and no file here changes.

A job kind's ``setup`` makes the deployment from the seed, ``run`` drives
one job and keeps what it produced, ``work`` gives the job's logical
sizes for the work counts, ``end_to_end`` turns the window's jobs into
the cell's end-to-end metrics, and ``check`` compares what the window's
jobs produced with ``chipbench.reference`` (see ``Number``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Number:
    """One compared number: correct while ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def limits(config: dict, traffic: dict, names: List[str]) -> Dict[str, float]:
    """Each number's limit: the configuration's, or the traffic mix's
    where the mix sets its own (training numbers read differently on a
    coreset of ~86 rows and on all 49,000)."""
    lim = dict(config["limits"], **traffic.get("limits", {}))
    return {n: float(lim[n]) for n in names}


class Job:
    """The parts of a job kind that have a general form."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, int(seed)

    def setup(self) -> None:
        pass

    def teardown(self) -> None:
        pass

    def faults(self, records: List[dict]) -> Dict[str, Dict[str, float]]:
        """Readings of faults planted in the reference's place, by fault
        and number; a kind whose faults need a run of their own has none
        here."""
        return {}

    def end_to_end(self, seconds: float, records: List[dict]
                   ) -> Dict[str, float]:
        """The mix's one metric: the window's whole time over the number
        of jobs it completed."""
        return {self.traffic["metric"]: seconds / len(records)}
