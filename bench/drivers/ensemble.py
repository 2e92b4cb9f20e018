"""The paper's evaluation unit: `sweep(schemes=("ours",), cache=None)`
over an ensemble of sampled instances, again and again.  The instance the
harness builds is the members stacked along the coflow axis
(``bench/gens/paper_va.py``); the driver splits it back into members of
the configuration's ``num_coflows`` rows.  The work of a call is the
flows of every member, and the check reads every member."""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import os

import numpy as np

from harness import reference as ref
from harness import registry
from harness.driver import LP_OVER_CCT_LIMIT, program_instance


def _bound(inst) -> float:
    """Algorithm 1's guarantee: 8K with every release at zero, else 8K+1."""
    return 8.0 * inst.num_cores + (1.0 if (inst.releases > 0).any() else 0.0)


def _exact_lps(members) -> list[float]:
    """The exact LP optimum of every member, solved in a pool of processes
    (one HiGHS solve each; a second or more at 100 coflows)."""
    workers = min(os.cpu_count() or 1, len(members))
    if workers <= 1:
        return [ref.exact_lp(m) for m in members]
    # Fresh interpreters: the parent's JAX threads are not forked.
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(workers, mp_context=ctx) as ex:
        return list(ex.map(ref.exact_lp, members))


class Driver(registry.driver("sweep")):
    """The sweep driver's call, its unit and its core tables, over every
    member of the ensemble."""

    def __init__(self, config: dict, traffic: dict, inst):
        self.config = config
        m = config["num_coflows"]
        if inst.num_coflows % m:
            raise ValueError(f"{inst.num_coflows} coflows are not members "
                             f"of {m} coflows each")
        # The traffic's ``members`` is the most a sweep takes (the tests'
        # tiny configurations have fewer).
        n = min(inst.num_coflows // m, traffic["members"])
        self.members = [inst.subset(np.arange(s, s + m))
                        for s in range(0, n * m, m)]
        self.inst = inst.subset(np.arange(n * m))
        self._pinsts = [program_instance(x) for x in self.members]
        self._exact = None

    def call(self):
        from repro.experiments import sweep

        return sweep(
            self._pinsts, schemes=("ours",), lp_method="batch",
            lp_iters=self.config["lp_iters"],
            discipline=self.config["discipline"], cache=None,
        )

    def work(self, out) -> int:
        """Flows the call scheduled, over every member."""
        return sum(int(cs.coflow.shape[0])
                   for rec in out.records
                   for cs in rec.results["ours"].core_schedules)

    def exact_lps(self) -> list[float]:
        """Every member's exact LP optimum (computed once per driver)."""
        if self._exact is None:
            self._exact = _exact_lps(self.members)
        return self._exact

    def check(self, outs: list, control: bool = False) -> dict:
        """Numbers compared, each ``(value, limit)``, over every member:
        the exact counts summed, the ratios at the worst member.  The
        first output is checked in full against the reference; the others
        must repeat it bit for bit.  ``control`` puts the reference
        computed in float32 in the program's place."""
        disc = self.config["discipline"]
        counts = dict(order_mismatch=0, flow_mismatch=0, cct_mismatch=0,
                      violations=0, repeat_mismatch=0)
        ratio, over, gap = [], [], []
        for b, (inst, rec, exact) in enumerate(zip(
                self.members, outs[0].records, self.exact_lps())):
            res = rec.results["ours"]
            order = np.asarray(res.order)
            lp_comp = np.asarray(rec.lp.completion, np.float64)
            _, want_cores = ref.schedule(inst, order, disc)
            got_cores = self._cores(res)
            got_ccts = np.asarray(res.ccts, np.float64)
            if control:
                _, got_cores = ref.schedule(inst, order, disc, np.float32)
                got_ccts = ref.ccts(inst.num_coflows, got_cores)
            want_ccts = ref.ccts(inst.num_coflows, want_cores)
            counts["order_mismatch"] += int(
                (order != np.argsort(lp_comp, kind="stable")).sum())
            counts["flow_mismatch"] += ref.flow_mismatch(
                ref.flat(got_cores), ref.flat(want_cores))
            counts["cct_mismatch"] += int((got_ccts != want_ccts).sum())
            counts["violations"] += ref.violations(inst, got_cores)
            counts["repeat_mismatch"] += sum(
                not np.array_equal(
                    np.asarray(o.records[b].results["ours"].ccts), res.ccts)
                for o in outs[1:])
            wcct = float(np.dot(inst.weights, got_ccts))
            obj = float(rec.lp.objective)
            ratio.append(wcct / obj)
            over.append(obj / wcct)
            gap.append(obj / exact - 1.0)
        out = {k: (v, 0) for k, v in counts.items()}
        out["bound_ratio"] = (max(ratio), _bound(self.inst))
        out["lp_over_cct"] = (max(over), LP_OVER_CCT_LIMIT)
        out["lp_gap"] = (max(gap), self.config["lp_gap_limit"])
        return out
