"""Offline planning: `sweep(schemes=("ours",), cache=None)` over one
instance, again and again; the work of a call is the flows it schedules."""

from __future__ import annotations

import numpy as np

from harness import reference as ref
from harness.driver import LP_OVER_CCT_LIMIT, bound, program_instance


class Driver:
    kind = "sweep"

    def __init__(self, config: dict, traffic: dict, inst):
        self.config = config
        self.inst = inst
        self._pinst = program_instance(inst)

    def call(self):
        from repro.experiments import sweep

        return sweep(
            [self._pinst], schemes=("ours",), lp_method="batch",
            lp_iters=self.config["lp_iters"],
            discipline=self.config["discipline"], cache=None,
        )

    def work(self, out) -> int:
        """Flows the call scheduled."""
        res = out.records[0].results["ours"]
        return sum(int(cs.coflow.shape[0]) for cs in res.core_schedules)

    def units(self, outs) -> int:
        """Per-layer metrics are per sweep."""
        return len(outs)

    @staticmethod
    def _cores(res) -> list[dict]:
        return [
            {"coflow": cs.coflow, "src": cs.src, "dst": cs.dst,
             "size": cs.size, "establish": cs.establish,
             "complete": cs.complete}
            for cs in res.core_schedules
        ]

    def check(self, outs: list, control: bool = False) -> dict:
        """Numbers compared, each ``(value, limit)``.  The first output is
        checked in full against the reference; the others must repeat it
        bit for bit (the window sends the same instance every call).
        ``control`` puts the reference computed in float32 in the
        program's place."""
        inst = self.inst
        rec = outs[0].records[0]
        res = rec.results["ours"]
        order = np.asarray(res.order)
        lp_comp = np.asarray(rec.lp.completion, np.float64)
        want_alloc, want_cores = ref.schedule(
            inst, order, self.config["discipline"])
        got_cores = self._cores(res)
        got_ccts = np.asarray(res.ccts, np.float64)
        if control:
            _, got_cores = ref.schedule(
                inst, order, self.config["discipline"], np.float32)
            got_ccts = ref.ccts(inst.num_coflows, got_cores)
        want_ccts = ref.ccts(inst.num_coflows, want_cores)
        wcct = float(np.dot(inst.weights, got_ccts))
        return {
            "order_mismatch": (int(
                (order != np.argsort(lp_comp, kind="stable")).sum()), 0),
            "flow_mismatch": (ref.flow_mismatch(
                ref.flat(got_cores), ref.flat(want_cores)), 0),
            "cct_mismatch": (int((got_ccts != want_ccts).sum()), 0),
            "violations": (ref.violations(inst, got_cores), 0),
            "repeat_mismatch": (sum(
                not np.array_equal(
                    np.asarray(o.records[0].results["ours"].ccts), res.ccts)
                for o in outs[1:]), 0),
            "bound_ratio": (wcct / float(rec.lp.objective), bound(inst)),
            "lp_over_cct": (float(rec.lp.objective) / wcct,
                            LP_OVER_CCT_LIMIT),
            "lp_gap": (float(rec.lp.objective) / ref.exact_lp(inst) - 1.0,
                       self.config["lp_gap_limit"]),
        }
