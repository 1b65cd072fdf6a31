"""Job kind ``regression``: one TreeCSS job on a numeric label,
``repro.core.treecss.run_pipeline`` with the split linear regression
(align -> coreset on (CT, label-bin) groups -> train -> score the test
rows), the same seeded deployment for every job of a run.  The check
compares the aligned ids, the coreset and every k-means answer behind
it, and the trained model with the plain references
(``chipbench.reference``, ``chipbench.regression_reference``)."""
from __future__ import annotations

import hashlib
from typing import Dict, List

import numpy as np

from chipbench import jobs, reference, regression_data
from chipbench import regression_reference as ref_lin
from chipbench.kinds.pipeline import Job as PipelineJob


def _digest(*arrays) -> str:
    h = hashlib.sha1()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


class Job(PipelineJob):
    """One TreeCSS job: the parties' raw id sets to a trained split
    linear regression and its test error, through ``run_pipeline``."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        if not self.css:
            raise ValueError("the regression kind runs TreeCSS variants "
                             f"only, not {traffic['variant']!r}")
        self._selected: Dict[str, tuple] = {}

    def setup(self) -> None:
        from repro.config import AlignOptions, EngineOptions
        from repro.core.splitnn import SplitNNConfig
        from repro.data.vertical import VerticalPartition

        c = self.config
        spec = regression_data.RegressionTable(**c["dataset"])
        self.train, self.test = regression_data.deployment(
            spec, c["parties"], c["train_share"], self.seed)
        cols = np.cumsum([0] + [f.shape[1] for f in self.train.features])
        slices = [slice(int(a), int(b)) for a, b in zip(cols[:-1], cols[1:])]
        self.train_part = VerticalPartition(self.train.features,
                                            self.train.labels, slices)
        self.test_part = VerticalPartition(self.test.features,
                                           self.test.labels, slices)
        m = c["model"]
        # convergence_eps 0 never stops early: every job trains exactly
        # ``epochs`` epochs
        self.cfg = SplitNNConfig(
            model=m["kind"], n_classes=0, lr=m["lr"],
            batch_size=m["batch_size"], max_epochs=self.traffic["epochs"],
            convergence_eps=0.0, seed=self.seed)
        self.options = EngineOptions(**c["engine"])
        self.align = AlignOptions(overlap=c["overlap"], **c["align"])

    def run(self) -> dict:
        from repro.core.treecss import run_pipeline

        rep = run_pipeline(
            self.train_part, self.test_part, self.cfg,
            variant=self.traffic["variant"],
            clusters_per_client=self.traffic["clusters_per_client"],
            kmeans_impl=self.config["kmeans_impl"], seed=self.seed,
            options=self.options, align=self.align)
        return {"intersection": np.asarray(rep.mpsi.intersection),
                "losses": list(rep.train.losses), "epochs": rep.train.epochs,
                "metric": float(rep.metric), "n_train": int(rep.n_train),
                "params": ref_lin.leaves(rep.train.params),
                "coreset": (np.asarray(rep.coreset.indices),
                            np.asarray(rep.coreset.weights),
                            [(np.asarray(l.assign), np.asarray(l.sq_dist),
                              np.asarray(l.centroids))
                             for l in rep.coreset.local])}

    def work(self, rec: dict) -> dict:
        """Logical sizes of one job: linreg is a bottom of one output per
        party and no hidden layer."""
        out = super().work(rec)
        out.update(bottom=1, hidden=0, n_out=1)
        return out

    # -------------------------------------------------------- the check

    def select(self, local, labels):
        """The reference's coreset from one clustering, kept by the
        clustering's bytes: every job of a run clusters the same rows."""
        key = _digest(*[a for a, _, _ in local], *[s for _, s, _ in local])
        if key not in self._selected:
            self._selected[key] = ref_lin.select_coreset(
                [a for a, _, _ in local], [s for _, s, _ in local], labels,
                self._k(local))
        return self._selected[key]

    def _reference_run(self, feats, labels, records, dtype: str,
                       half_batch: bool):
        """Reference training on the coreset the reference selects from
        the first job's clustering, and its predictions of the test
        rows."""
        idx, w = self.select(records[0]["coreset"][2], labels)
        m = self.config["model"]
        ref = ref_lin.train(
            [f[idx] for f in feats], labels[idx], w, seed=self.seed,
            epochs=self.traffic["epochs"], batch=m["batch_size"], lr=m["lr"],
            precision=self.config["matmul_precision"], dtype=dtype,
            half_batch=half_batch)
        pred = ref_lin.predict(ref[1], self.test.features,
                               precision=self.config["matmul_precision"])
        return ref, pred

    def mse(self, pred: np.ndarray) -> float:
        y = self.test.labels.astype(np.float64)
        return float(np.mean((pred.astype(np.float64) - y) ** 2))

    def train_numbers(self, records: List[dict], ref, ref_mse: float
                      ) -> Dict[str, float]:
        """The training numbers of each job against one reference run:
        the widest gap of an epoch's mean loss, as a share of the
        reference's first-epoch loss; the worst leaf's gap in the norm of
        its change from the initial parameters, as a share of the larger
        of its own change and the median leaf's; and the gap in test MSE
        as a share of the test target's variance."""
        p0, p_ref, losses_ref = ref
        l0 = ref_lin.leaves(p0)
        lr_ = ref_lin.leaves(p_ref)
        ch_ref = {k: float(np.linalg.norm(lr_[k] - l0[k])) for k in l0}
        ch_med = float(np.median(list(ch_ref.values())))
        lref = np.asarray(losses_ref, np.float64)
        var = float(np.var(self.test.labels.astype(np.float64)))
        loss_gap = change_gap = mse_gap = 0.0
        for rec in records:
            lp = np.asarray(rec["losses"], np.float64)
            if lp.shape != lref.shape:
                loss_gap = np.inf
            else:
                loss_gap = max(loss_gap, float(np.max(
                    np.abs(lp - lref)) / abs(lref[0])))
            for k in l0:
                cp = float(np.linalg.norm(rec["params"][k] - l0[k]))
                change_gap = max(change_gap, abs(cp - ch_ref[k])
                                 / max(ch_ref[k], ch_med))
            mse_gap = max(mse_gap, abs(rec["metric"] - ref_mse) / var)
        return {"train_loss_gap": loss_gap, "train_change_gap": change_gap,
                "test_mse_gap": mse_gap}

    def check(self, records: List[dict]) -> List[jobs.Number]:
        inter, feats, labels = self.reference_data()
        names = ["align_ids_wrong"]
        val = {"align_ids_wrong": max(
            float(np.setxor1d(r["intersection"], inter).size)
            for r in records)}
        # a clustering of other rows than the aligned ones cannot be
        # compared: every later number then reads infinite
        rows_ok = all(a.shape[0] == labels.shape[0]
                      for r in records for a, _, _ in r["coreset"][2])
        later = ["coreset_rows_wrong", "kmeans_assign_gap",
                 "kmeans_sq_dist_gap", "kmeans_lloyd_gap", "train_loss_gap",
                 "train_change_gap", "test_mse_gap"]
        if rows_ok:
            val.update(self.coreset_numbers(records, feats, labels))
            ref, pred = self.reference_run(feats, labels, records, "float32")
            val.update(self.train_numbers(records, ref, self.mse(pred)))
        names += later
        for n in later:
            val.setdefault(n, np.inf)
        lim = jobs.limits(self.config, self.traffic, names)
        return [jobs.Number(n, float(val[n]), lim[n]) for n in names]

    def coreset_numbers(self, records, feats, labels) -> Dict[str, float]:
        wrong = 0.0
        for rec in records:
            idx, w, local = rec["coreset"]
            ridx, rw = self.select(local, labels)
            if idx.shape != ridx.shape:
                bad = abs(idx.size - ridx.size) + np.setxor1d(idx, ridx).size
            else:
                bad = int(np.sum(idx != ridx)) + int(np.sum(
                    np.abs(w - rw) > 1e-5 * np.maximum(np.abs(rw), 1.0)))
            wrong = max(wrong, float(bad))
        out = {"coreset_rows_wrong": wrong}
        # the worst over the jobs is the worst over their distinct
        # clusterings
        distinct = {_digest(*[x for party in r["coreset"][2] for x in party]):
                    r["coreset"][2] for r in records}
        out.update(self.kmeans_numbers(list(distinct.values()), feats))
        return out

    def control(self, records: List[dict]) -> List[jobs.Number]:
        """The reference put in the program's place one step lower in
        precision, bfloat16 for every array: the split linear regression
        trained so, and each party's assignment made so from the first
        job's centroids; held to the same numbers and limits."""
        _, feats, labels = self.reference_data()
        ref, pred = self.reference_run(feats, labels, records, "float32")
        low, low_pred = self.reference_run(feats, labels, records,
                                           "bfloat16")
        p0, p_low, losses_low = low
        fake = [{"losses": losses_low, "params": ref_lin.leaves(p_low),
                 "metric": self.mse(low_pred)}]
        val = self.train_numbers(fake, ref, self.mse(pred))
        local = [(*reference.nearest(f, c, "bfloat16"), c)
                 for f, (_, _, c) in zip(feats, records[0]["coreset"][2])]
        val.update(self.kmeans_numbers([local], feats))
        lim = jobs.limits(self.config, self.traffic, list(val))
        return [jobs.Number(n, float(v), lim[n]) for n, v in val.items()]

    def faults(self, records: List[dict]) -> Dict[str, Dict[str, float]]:
        """Readings of faults planted in the reference's place, at the
        cell's size: half of every batch left out, the mean taken over
        the rest (the training numbers); every prediction negated where
        it is produced (the test MSE); every hundredth point moved to the
        next cluster where the assignment is produced (the k-means
        numbers of the first job's clustering).  A step that returns its
        state unchanged reads 1 on ``train_change_gap`` by its measure
        and needs no run."""
        _, feats, labels = self.reference_data()
        ref, pred = self.reference_run(feats, labels, records, "float32")
        ref_mse = self.mse(pred)
        (_, p_half, losses_half), half_pred = self.reference_run(
            feats, labels, records, "float32", half_batch=True)
        half = {"losses": losses_half, "params": ref_lin.leaves(p_half),
                "metric": self.mse(half_pred)}
        var = float(np.var(self.test.labels.astype(np.float64)))
        moved = []
        for a, s, c in records[0]["coreset"][2]:
            a = a.copy()
            a[::100] = (a[::100] + 1) % c.shape[0]
            moved.append((a, s, c))
        return {"half_batch": self.train_numbers([half], ref, ref_mse),
                "predictions_negated": {"test_mse_gap": abs(
                    self.mse(-pred) - ref_mse) / var},
                "points_moved": self.kmeans_numbers([moved], feats)}
