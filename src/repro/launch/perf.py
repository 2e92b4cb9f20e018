"""Perf-iteration driver for the roofline hillclimb.

Runs one (arch x shape) cell with config/step overrides and prints the
three roofline terms next to the recorded baseline, so each
hypothesis -> change -> measure cycle is one command:

  python -m repro.launch.perf --arch xlstm-1.3b --shape prefill_32k \
      --override mlstm_chunk=1024 --tag chunk1024

Also home of `measured_roofline`: the HLO-text -> roofline-distance
bridge the micro benchmarks use to report how far a measured wall time
sits from the cost model's hardware bound (`repro.launch.hlo_cost` for
the static counts, `repro.launch.roofline` for the bound).
"""

import argparse
import json
import os
from pathlib import Path

__all__ = ["measured_roofline", "main"]


def measured_roofline(
    hlo_text: str, measured_s: float, device_kind: str
) -> dict:
    """Roofline terms + achieved fraction for one compiled program.

    ``hlo_text`` is the post-compile HLO (``lowered.compile().as_text()``);
    ``measured_s`` the measured wall time of one execution on a device of
    ``device_kind`` (whose peaks `repro.launch.roofline.hardware` looks
    up; an unknown kind raises).  Returns the `roofline_terms` dict
    extended with the static counts and ``roofline_frac = bound_s /
    measured_s`` (1.0 == at the hardware roofline; tiny values ==
    latency/overhead bound).
    """
    from repro.launch import hlo_cost, roofline

    cost = hlo_cost.analyze(hlo_text)
    terms = roofline.roofline_terms(
        cost.flops, cost.bytes, cost.collective_total,
        roofline.hardware(device_kind),
    )
    terms["flops"] = cost.flops
    terms["bytes"] = cost.bytes
    terms["collective_bytes"] = cost.collective_total
    terms["measured_s"] = measured_s
    terms["roofline_frac"] = roofline.roofline_fraction(
        terms["bound_s"], measured_s
    )
    return terms


def main():
    # Host-device fanout must be set before the first jax import; keep the
    # mutation inside main() so merely importing this module (e.g. for
    # `measured_roofline`) never rewrites the process environment.
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override key=value (repeatable)")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--mixed-precision", action="store_true")
    ap.add_argument(
        "--rules-override", action="append", default=[],
        help="sharding-rule override, e.g. seq=none or seq=model",
    )
    ap.add_argument("--baseline-dir", default="results/dryrun")
    ap.add_argument("--out", default="results/perf")
    ap.add_argument("--tag", default="iter")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                v = {"true": True, "false": False}.get(v.lower(), v)
        overrides[k] = v

    rules_overrides = {}
    for kv in args.rules_override:
        k, v = kv.split("=", 1)
        if k == "param_tp":
            rules_overrides[k] = v
        else:
            rules_overrides[k] = ((),) if v == "none" else ((v,), ())

    from repro.launch.dryrun import run_cell

    res = run_cell(
        args.arch,
        args.shape,
        args.multi_pod,
        zero1=args.zero1,
        num_microbatches=args.microbatches,
        cfg_overrides=overrides or None,
        mixed_precision=args.mixed_precision,
        rules_overrides=rules_overrides or None,
    )
    mesh = "multi" if args.multi_pod else "single"
    base_path = Path(args.baseline_dir) / f"{args.arch}__{args.shape}__{mesh}.json"
    base = json.load(open(base_path)) if base_path.exists() else None

    def fmt(d):
        r = d["roofline"]
        return (
            f"c={r['compute_s']:.4f} m={r['memory_s']:.4f} "
            f"n={r['collective_s']:.4f} bound={r['bound_s']:.4f} "
            f"({r['dominant']}) peak={d['memory']['peak_estimate_bytes']/2**30:.2f}GiB"
        )

    if base:
        print(f"baseline: {fmt(base)}")
    print(f"{args.tag:>8s}: {fmt(res)}")
    if base:
        b, a = base["roofline"]["bound_s"], res["roofline"]["bound_s"]
        print(f"bound delta: {b:.4f} -> {a:.4f}  ({(1 - a / b) * 100:+.1f}%)")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    tagp = out / f"{args.arch}__{args.shape}__{mesh}__{args.tag}.json"
    res["overrides"] = overrides
    res["rules_overrides"] = {k: str(v) for k, v in rules_overrides.items()}
    res["mixed_precision"] = args.mixed_precision
    tagp.write_text(json.dumps(res, indent=1))
    print(f"saved {tagp}")


if __name__ == "__main__":
    main()
