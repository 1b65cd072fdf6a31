"""Job kind ``pipeline``: one TreeCSS job, ``repro.core.treecss.
run_pipeline`` on the configuration's deployment (align -> coreset ->
train -> evaluate), the same seeded deployment for every job of a run.
The check compares the aligned ids, the coreset and every k-means answer
behind it, and the trained model with ``chipbench.reference``."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import datagen, jobs, reference


class Job(jobs.Job):
    """One TreeCSS job: the parties' raw id sets to a trained, evaluated
    split model, through ``run_pipeline``."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self.css = traffic["variant"].endswith("css")
        self._refs: Dict[tuple, tuple] = {}

    def setup(self) -> None:
        from repro.config import AlignOptions, EngineOptions
        from repro.core.splitnn import SplitNNConfig
        from repro.data.vertical import VerticalPartition

        c = self.config
        spec = datagen.Table(**c["dataset"])
        self.train, self.test = datagen.deployment(
            spec, c["parties"], c["train_share"], self.seed)
        cols = np.cumsum([0] + [f.shape[1] for f in self.train.features])
        slices = [slice(int(a), int(b)) for a, b in zip(cols[:-1], cols[1:])]
        self.train_part = VerticalPartition(self.train.features,
                                            self.train.labels, slices)
        self.test_part = VerticalPartition(self.test.features,
                                           self.test.labels, slices)
        m = c["model"]
        # convergence_eps 0 never stops early: every job trains exactly
        # ``epochs`` epochs, whatever order its sums are reduced in
        self.cfg = SplitNNConfig(
            model=m["kind"], n_classes=c["dataset"]["n_classes"],
            bottom_dim=m["bottom_dim"], hidden_dim=m["hidden_dim"],
            lr=m["lr"], batch_size=m["batch_size"],
            max_epochs=self.traffic["epochs"], convergence_eps=0.0,
            seed=self.seed)
        self.options = EngineOptions(**c["engine"])
        self.align = AlignOptions(overlap=c["overlap"], **c["align"])

    def run(self) -> dict:
        from repro.core.treecss import run_pipeline

        rep = run_pipeline(
            self.train_part, self.test_part, self.cfg,
            variant=self.traffic["variant"],
            clusters_per_client=self.traffic.get("clusters_per_client", 12),
            kmeans_impl=self.config["kmeans_impl"], seed=self.seed,
            options=self.options, align=self.align)
        rec = {"intersection": np.asarray(rep.mpsi.intersection),
               "losses": list(rep.train.losses), "epochs": rep.train.epochs,
               "metric": float(rep.metric), "n_train": int(rep.n_train),
               "params": reference.leaves(rep.train.params)}
        if rep.coreset is not None:
            rec["coreset"] = (np.asarray(rep.coreset.indices),
                              np.asarray(rep.coreset.weights),
                              [(np.asarray(l.assign), np.asarray(l.sq_dist),
                                np.asarray(l.centroids))
                               for l in rep.coreset.local])
        return rec

    def work(self, rec: dict) -> dict:
        """Logical sizes of one job for the work counts."""
        widths = [f.shape[1] for f in self.train.features]
        out = {"widths": widths, "n_align": int(rec["intersection"].size),
               "n_train": rec["n_train"], "epochs": rec["epochs"],
               "n_test": self.test.n, "batch": self.cfg.batch_size,
               "bottom": self.cfg.bottom_dim, "hidden": self.cfg.hidden_dim,
               "n_out": 1}
        if self.css:
            out["kmeans"] = {"k": self.traffic.get("clusters_per_client", 12),
                             "iters": self.config["kmeans_iters"]}
        return out

    # -------------------------------------------------------- the check

    def reference_data(self):
        """The aligned rows, the reference intersection and (TreeCSS)
        nothing else: everything the references need from the seed."""
        c = self.config
        sets, _ = datagen.id_universe(c["parties"], self.train.n,
                                      c["overlap"], self.seed)
        inter = reference.intersect(sets)
        rows = reference.aligned_rows(sets[0], inter)
        feats = [f[rows] for f in self.train.features]
        return inter, feats, self.train.labels[rows]

    def reference_train(self, feats, labels, weights, dtype: str,
                        half_batch: bool = False):
        """The split MLP trained in ``dtype`` at the matrix-product
        precision the configuration states (on a TPU, "default" is one
        bfloat16 pass, as the program's float32 products are)."""
        m = self.config["model"]
        return reference.train(
            feats, labels.astype(np.float32), weights, seed=self.seed,
            epochs=self.traffic["epochs"], batch=m["batch_size"], lr=m["lr"],
            bottom=m["bottom_dim"], hidden=m["hidden_dim"], dtype=dtype,
            precision=self.config["matmul_precision"], half_batch=half_batch)

    def train_numbers(self, records: List[dict], ref, ref_acc: float
                      ) -> Dict[str, float]:
        """The training numbers of each job against one reference run:
        the widest gap of an epoch's mean loss, as a share of the
        reference's first-epoch loss (the loss falls by orders of
        magnitude, so a share of each epoch's own would measure noise
        on a near-zero number); the worst leaf's gap in the norm of its
        change from the initial parameters; and the gap in test
        accuracy."""
        p0, p_ref, losses_ref, g0 = ref
        l0 = reference.leaves(p0)
        lr_ = reference.leaves(p_ref)
        gn = {k: float(np.linalg.norm(v)) for k, v in
              reference.leaves(g0).items()}
        g_med = float(np.median(list(gn.values())))
        # leaves the reference does not move but by rounding are left out
        keep = [k for k in l0 if gn[k] >= 1e-3 * g_med]
        ch_ref = {k: float(np.linalg.norm(lr_[k] - l0[k])) for k in keep}
        ch_med = float(np.median(list(ch_ref.values())))
        lref = np.asarray(losses_ref, np.float64)
        loss_gap = change_gap = acc_gap = 0.0
        for rec in records:
            lp = np.asarray(rec["losses"], np.float64)
            if lp.shape != lref.shape:
                loss_gap = np.inf
            else:
                loss_gap = max(loss_gap, float(np.max(
                    np.abs(lp - lref)) / abs(lref[0])))
            for k in keep:
                cp = float(np.linalg.norm(rec["params"][k] - l0[k]))
                change_gap = max(change_gap, abs(cp - ch_ref[k])
                                 / max(ch_ref[k], ch_med))
            acc_gap = max(acc_gap, abs(rec["metric"] - ref_acc))
        return {"train_loss_gap": loss_gap, "train_change_gap": change_gap,
                "test_accuracy_gap": acc_gap}

    def reference_run(self, feats, labels, records, dtype: str,
                      half_batch: bool = False):
        """Reference training (and its accuracy) on what the reference
        selects: all aligned rows, or the coreset that steps 2, 4 and 5
        pick from the first job's clustering.  Kept per precision, fault
        and first job, so the check, the control and the faults train
        once."""
        key = (dtype, half_batch, id(records[0]))
        if key not in self._refs:
            self._refs[key] = self._reference_run(feats, labels, records,
                                                  dtype, half_batch)
        return self._refs[key]

    def _reference_run(self, feats, labels, records, dtype: str,
                       half_batch: bool):
        if self.css:
            _, _, local = records[0]["coreset"]
            idx, w = reference.select_coreset(
                [a for a, _, _ in local], [s for _, s, _ in local], labels,
                self._k(local))
            feats = [f[idx] for f in feats]
            labels = labels[idx]
        else:
            w = np.ones(labels.shape[0], np.float32)
        ref = self.reference_train(feats, labels, w, dtype, half_batch)
        logits = reference.predict_logits(
            ref[1], self.test.features,
            precision=self.config["matmul_precision"])
        acc = float(np.mean((logits > 0) == (self.test.labels == 1)))
        return ref, acc

    @staticmethod
    def _k(local) -> int:
        return int(local[0][2].shape[0])

    def check(self, records: List[dict]) -> List[jobs.Number]:
        inter, feats, labels = self.reference_data()
        names = ["align_ids_wrong"]
        val = {"align_ids_wrong": max(
            float(np.setxor1d(r["intersection"], inter).size)
            for r in records)}
        # a clustering of other rows than the aligned ones cannot be
        # compared: every later number then reads infinite
        rows_ok = not self.css or all(
            a.shape[0] == labels.shape[0]
            for r in records for a, _, _ in r["coreset"][2])
        later = []
        if self.css:
            later += ["coreset_rows_wrong", "kmeans_assign_gap",
                      "kmeans_sq_dist_gap", "kmeans_lloyd_gap"]
            if rows_ok:
                val.update(self.coreset_numbers(records, feats, labels))
        later += ["train_loss_gap", "train_change_gap", "test_accuracy_gap"]
        if rows_ok:
            ref, acc = self.reference_run(feats, labels, records, "float32")
            val.update(self.train_numbers(records, ref, acc))
        names += later
        for n in later:
            val.setdefault(n, np.inf)
        lim = jobs.limits(self.config, self.traffic, names)
        return [jobs.Number(n, float(val[n]), lim[n]) for n in names]

    def coreset_numbers(self, records, feats, labels) -> Dict[str, float]:
        wrong = 0.0
        for rec in records:
            idx, w, local = rec["coreset"]
            k = self._k(local)
            ridx, rw = reference.select_coreset(
                [a for a, _, _ in local], [s for _, s, _ in local], labels, k)
            if idx.shape != ridx.shape:
                bad = abs(idx.size - ridx.size) + np.setxor1d(idx, ridx).size
            else:
                bad = int(np.sum(idx != ridx)) + int(np.sum(
                    np.abs(w - rw) > 1e-5 * np.maximum(np.abs(rw), 1.0)))
            wrong = max(wrong, float(bad))
        out = {"coreset_rows_wrong": wrong}
        out.update(self.kmeans_numbers([r["coreset"][2] for r in records],
                                       feats))
        return out

    @staticmethod
    def kmeans_numbers(locals_, feats) -> Dict[str, float]:
        """The worst of each k-means number over the jobs' clusterings
        (one (assign, sq_dist, centroids) per party in each)."""
        gap = {"assign": 0.0, "sq_dist": 0.0, "lloyd": 0.0}
        for local in locals_:
            for f, (a, s, c) in zip(feats, local):
                g = reference.clustering_gaps(f, a, s, c)
                gap = {n: max(v, g[n]) for n, v in gap.items()}
        return {f"kmeans_{n}_gap": v for n, v in gap.items()}

    def control(self, records: List[dict]) -> List[jobs.Number]:
        """The reference put in the program's place one step lower in
        precision, bfloat16 for every array: the split MLP trained so
        and, in TreeCSS, each party's assignment made so from the first
        job's centroids; held to the same numbers and limits."""
        _, feats, labels = self.reference_data()
        ref, acc = self.reference_run(feats, labels, records, "float32")
        low, low_acc = self.reference_run(feats, labels, records, "bfloat16")
        p0, p_low, losses_low, _ = low
        fake = [{"losses": losses_low, "params": reference.leaves(p_low),
                 "metric": low_acc}]
        val = self.train_numbers(fake, ref, acc)
        if self.css:
            local = [(*reference.nearest(f, c, "bfloat16"), c)
                     for f, (_, _, c) in zip(feats, records[0]["coreset"][2])]
            val.update(self.kmeans_numbers([local], feats))
        lim = jobs.limits(self.config, self.traffic, list(val))
        return [jobs.Number(n, float(v), lim[n]) for n, v in val.items()]

    def faults(self, records: List[dict]) -> Dict[str, Dict[str, float]]:
        """Readings of faults planted in the reference's place, at the
        cell's size: half of every batch left out, the mean taken over
        the rest (the training numbers); every test score negated where
        it is produced (the test accuracy); and, in TreeCSS, every
        hundredth point moved to the next cluster where the assignment
        is produced (the k-means numbers of the first job's clustering).
        A step that returns its state unchanged reads 1 on
        ``train_change_gap`` by its measure and needs no run."""
        _, feats, labels = self.reference_data()
        ref, acc = self.reference_run(feats, labels, records, "float32")
        (_, p_half, losses_half, _), half_acc = self.reference_run(
            feats, labels, records, "float32", half_batch=True)
        half = {"losses": losses_half, "params": reference.leaves(p_half),
                "metric": half_acc}
        # negated scores flip every prediction: accuracy 1 - acc
        out = {"half_batch": self.train_numbers([half], ref, acc),
               "scores_negated": {"test_accuracy_gap": abs(1 - 2 * acc)}}
        if self.css:
            local = records[0]["coreset"][2]
            moved = []
            for a, s, c in local:
                a = a.copy()
                a[::100] = (a[::100] + 1) % c.shape[0]
                moved.append((a, s, c))
            out["points_moved"] = self.kmeans_numbers([moved], feats)
        return out
