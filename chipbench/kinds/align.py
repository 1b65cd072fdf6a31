"""Job kind ``align``: one whole Tree-MPSI alignment of the
configuration's id sets through ``repro.psi.run_psi``.  The check
compares every round's pairing and per-pair output, and the final set,
with ``chipbench.reference``."""
from __future__ import annotations

import hashlib
from typing import List, Optional

import numpy as np

from chipbench import datagen, jobs, reference


def _digest(a: np.ndarray) -> str:
    return hashlib.sha1(np.ascontiguousarray(a, np.int64).tobytes()
                        ).hexdigest()


class Job(jobs.Job):
    """One whole Tree-MPSI alignment of the configuration's id sets
    through ``repro.psi.run_psi``; every round's per-pair output is kept
    by a recorder around the round executor and compared after the
    window."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self._rounds: Optional[list] = None
        self._reference = None

    def setup(self) -> None:
        from repro.config import AlignOptions
        from repro.psi import engine

        c = self.config
        self.sets, _ = datagen.id_universe(c["parties"], c["ids_per_party"],
                                           c["overlap"], self.seed)
        self.options = AlignOptions(overlap=c["overlap"], **c["align"])
        executor = engine.oprf_round if c["align"]["protocol"] == "oprf" \
            else engine.match_round
        self._executor = executor

        def recorded(*args, **kw):
            out = executor(*args, **kw)
            if self._rounds is not None:         # kept, digested later
                self._rounds.append(list(out.intersections))
            return out
        setattr(engine, executor.__name__, recorded)

    def run(self) -> dict:
        from repro.psi import run_psi

        self._rounds = []
        st = run_psi(self.sets, topology=self.traffic["topology"],
                     options=self.options)
        rec = {"intersection": np.asarray(st.intersection),
               "schedule": [list(map(tuple, r)) for r in st.schedule],
               "rounds": self._rounds}
        self._rounds = None
        return rec

    def teardown(self) -> None:
        from repro.psi import engine

        setattr(engine, self._executor.__name__, self._executor)

    def reference_rounds(self):
        if self._reference is None:
            self._reference = reference.tree_rounds(
                self.sets, self.config["align"]["protocol"])
        return self._reference

    def work(self, rec: dict) -> dict:
        """Real keys (both sides) of every pair merge of the alignment."""
        held = {i: len(np.unique(s)) for i, s in enumerate(self.sets)}
        keys = []
        for rnd in self.reference_rounds():
            for s, r, inter in rnd:
                keys.append(held[s] + held[r])
            for _, r, inter in rnd:
                held[r] = inter.size
        return {"merge_keys": keys, "n_align": int(rec["intersection"].size)}

    def check(self, records: List[dict]) -> List[jobs.Number]:
        rounds = self.reference_rounds()
        want_sched = [[(s, r) for s, r, _ in rnd] for rnd in rounds]
        want = [[_digest(i) for _, _, i in rnd] for rnd in rounds]
        final = rounds[-1][-1][2]
        sched_wrong = rounds_wrong = ids_wrong = 0.0
        for rec in records:
            sched_wrong = max(sched_wrong,
                              float(rec["schedule"] != want_sched))
            got = [[_digest(i) for i in rnd] for rnd in rec["rounds"]]
            bad = sum(a != b for ga, wa in zip(got, want)
                      for a, b in zip(ga, wa))
            bad += abs(sum(map(len, got)) - sum(map(len, want)))
            rounds_wrong = max(rounds_wrong, float(bad))
            ids_wrong = max(ids_wrong, float(
                np.setxor1d(rec["intersection"], final).size))
        names = ["align_schedule_wrong", "align_rounds_wrong",
                 "align_ids_wrong"]
        lim = jobs.limits(self.config, self.traffic, names)
        vals = [sched_wrong, rounds_wrong, ids_wrong]
        return [jobs.Number(n, v, lim[n]) for n, v in zip(names, vals)]

    def control(self, records: List[dict], bits: int = 32
                ) -> List[jobs.Number]:
        """Tree-MPSI on ``bits``-bit keyed hashes of the ids in place of
        the 62-bit PRF tags: hash collisions let ids through that one
        side does not hold, which breaks the exact intersection."""
        def h32(a):                      # splitmix64, top ``bits`` bits
            x = a.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
            x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            return (x ^ (x >> np.uint64(31))) >> np.uint64(64 - bits)

        holdings = {i: np.unique(s) for i, s in enumerate(self.sets)}
        rounds = []
        for rnd in self.reference_rounds():
            out = []
            for s, r, _ in rnd:
                keep = np.isin(h32(holdings[r]), h32(holdings[s]))
                out.append((s, r, holdings[r][keep]))
            for _, r, i in out:
                holdings[r] = i
            rounds.append(out)
        fake = [{"intersection": rounds[-1][-1][2],
                 "schedule": [[(s, r) for s, r, _ in rnd] for rnd in rounds],
                 "rounds": [[i for _, _, i in rnd] for rnd in rounds]}]
        return self.check(fake)
