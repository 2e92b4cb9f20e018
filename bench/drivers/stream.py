"""The online service: `stream()` over the first coflows by release,
admitted in equal arrival batches; the work of a call is its epochs."""

from __future__ import annotations

import numpy as np

from harness import reference as ref
from harness.driver import LP_OVER_CCT_LIMIT, bound, program_instance
from harness.gen import first_by_release


class Driver:
    kind = "stream"

    def __init__(self, config: dict, traffic: dict, inst):
        self.config = config
        self.inst = first_by_release(inst, traffic["coflows"])
        self._pinst = program_instance(self.inst)
        svc = config["service"]
        if not svc["preempt"]:
            raise ValueError("the stream reference replays preemption only")
        self.kw = dict(
            lp_method="batch", lp_iters=config["lp_iters"],
            lp_iters_warm=svc["lp_iters_warm"],
            discipline=config["discipline"], n_batches=traffic["n_batches"],
            pool_size=svc["pool_size"], preempt=True,
            warm_start=svc["warm_start"], epoch_mode=svc["epoch_mode"],
            admission="fifo",
        )
        #: Per call, the completion times each epoch's LP solve returned.
        self.lp_completions: dict[int, list] = {}

    def call(self):
        """One stream.  The epochs' LP solutions are not in its result, so
        the call reads each one's completion times as the solver returns
        them (the service turns the same array into host values at once;
        this reads that copy first), for the check that every epoch's
        order follows its LP."""
        from repro.core import lp
        from repro.experiments import stream

        solve, comps = lp.solve_subgradient_batch_arrays, []

        def reading(*a, **k):
            sol = solve(*a, **k)
            comps.append(np.asarray(sol.completion)[0])
            return sol

        lp.solve_subgradient_batch_arrays = reading
        try:
            out = stream(self._pinst, **self.kw)
        finally:
            lp.solve_subgradient_batch_arrays = solve
        self.lp_completions[id(out)] = comps
        return out

    def work(self, out) -> int:
        return len(out.epochs)

    def units(self, outs) -> int:
        """Per-layer metrics are per epoch."""
        return sum(len(o.epochs) for o in outs)

    def order_mismatch(self, out) -> int:
        """Epochs whose order is not the stable sort of their LP's
        completion times, plus LP solves with no epoch or none read."""
        solved = [e for e in out.epochs if e.lp_objective is not None]
        comps = self.lp_completions.get(id(out), [])
        bad = abs(len(solved) - len(comps))
        for e, comp in zip(solved, comps):
            act = np.asarray(e.actives)
            want = act[np.argsort(comp[:act.shape[0]], kind="stable")]
            bad += not np.array_equal(np.asarray(e.order), want)
        return bad

    def check(self, outs: list, control: bool = False) -> dict:
        inst = self.inst
        out = outs[0]
        orders = [np.asarray(e.order) for e in out.epochs]
        replay = dict(n_batches=self.kw["n_batches"],
                      pool_size=self.kw["pool_size"],
                      discipline=self.kw["discipline"])
        want, want_finish = ref.replay_stream(inst, orders, **replay)
        got = [{"time": e.time, "actives": np.asarray(e.actives),
                "ccts": np.asarray(e.ccts, np.float64)} for e in out.epochs]
        got_finish = np.asarray(out.finish, np.float64)
        if control:
            try:
                got, got_finish = ref.replay_stream(
                    inst, orders, dtype=np.float32, **replay)
            except RuntimeError:  # never drains: nothing matches
                got, got_finish = [], np.zeros_like(want_finish)
        bad_epochs = abs(len(got) - len(want)) + sum(
            not (g["time"] == w["time"]
                 and np.array_equal(g["actives"], w["actives"])
                 and np.array_equal(g["ccts"], w["ccts"]))
            for g, w in zip(got, want)
        )
        ratios, gaps = [], []
        for e, w in zip(out.epochs, want):
            if e.lp_objective is None:
                continue
            ratios.append(float(np.dot(w["instance"].weights, e.ccts))
                          / e.lp_objective)
            gaps.append(e.lp_objective / ref.exact_lp(w["instance"]) - 1)
        return {
            "order_mismatch": (self.order_mismatch(out), 0),
            "epoch_mismatch": (int(bad_epochs), 0),
            "finish_mismatch": (int((got_finish != want_finish).sum()), 0),
            "repeat_mismatch": (sum(
                not np.array_equal(np.asarray(o.finish), out.finish)
                for o in outs[1:]), 0),
            "bound_ratio": (max(ratios), bound(inst)),
            "lp_over_cct": (1.0 / min(ratios), LP_OVER_CCT_LIMIT),
            "lp_gap": (max(gaps), self.config["lp_gap_limit"]),
        }
