import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ 512 placeholder host devices MUST be requested before any jax import
#   locks the device count — keep those the first two lines of this module.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16x16 single-pod or 2x16x16
multi-pod), constructs sharding-annotated ShapeDtypeStruct inputs (zero
allocation), lowers the appropriate step function (train_step / prefill /
serve_step), compiles it, and records:

  * memory_analysis()  — per-device bytes (proves the cell fits HBM),
  * cost_analysis()    — per-device FLOPs / bytes accessed,
  * collective bytes   — parsed from the post-SPMD HLO (launch/roofline.py),
  * the three roofline terms + dominant bottleneck.

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system — the run aborts loudly.

Usage:
  python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out results/dryrun
"""

import argparse
import json
import time
import traceback
from pathlib import Path


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    remat: str = "unit",
    zero1: bool = False,
    num_microbatches: int = 0,  # 0 = auto
    save_hlo: str | None = None,
    cfg_overrides: dict | None = None,  # perf-iteration knobs
    mixed_precision: bool = False,  # bf16 params + f32 master (train)
    rules_overrides: dict | None = None,  # sharding-rule overrides
) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import (
        V5E, collective_bytes, model_flops, roofline_terms,
    )
    from repro.launch.sharding import ShardingRules, activate
    from repro.launch.specs import (
        auto_mode, batch_specs, cache_specs, decode_batch_specs, opt_specs,
        param_specs, sds,
    )
    from repro.launch.steps import (
        default_optimizer, make_prefill_step, make_serve_step, make_train_step,
    )
    from repro.models.model import build_model
    from jax.sharding import NamedSharding, PartitionSpec as P

    import dataclasses

    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rules = ShardingRules(mesh, overrides=rules_overrides)
    if cfg.num_experts:
        # Align dispatch groups with the data-parallel shards.
        data_ways = rules.sizes.get("data", 1) * rules.sizes.get("pod", 1)
        cfg = dataclasses.replace(cfg, moe_groups=data_ways)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    model = build_model(cfg)
    t0 = time.perf_counter()

    mode = auto_mode(model, rules, "train" if shape.kind == "train" else "serve")
    if num_microbatches == 0:
        # Auto: bound live tokens/device (MoE dispatch buffers scale with
        # live tokens x top_k; dense trains gain activation headroom too).
        if shape.kind == "train":
            target = 8192 if cfg.num_experts else 16384
            data_ways = rules.sizes.get("data", 1) * rules.sizes.get("pod", 1)
            tokens_per_dev = shape.global_batch * shape.seq_len // data_ways
            num_microbatches = max(1, tokens_per_dev // target)
            num_microbatches = min(
                num_microbatches, max(shape.global_batch // data_ways, 1)
            )
        else:
            num_microbatches = 1
    with activate(rules):
        if shape.kind == "train":
            import dataclasses as _dc

            opt = default_optimizer()
            if mixed_precision:
                opt = _dc.replace(opt, master_weights=True)
            step = make_train_step(model, opt, num_microbatches=num_microbatches)
            p = param_specs(
                model, rules, mode=mode,
                dtype=jnp.bfloat16 if mixed_precision else None,
            )
            o = opt_specs(model, rules, opt, zero1=zero1, mode=mode)
            b = batch_specs(cfg, shape, rules, with_labels=True)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(p, o, b)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            # Serving path: weights in bf16.
            p = param_specs(model, rules, mode=mode, dtype=jnp.bfloat16)
            b = batch_specs(cfg, shape, rules, with_labels=False)
            lowered = jax.jit(step).lower(p, b)
        else:  # decode
            step = make_serve_step(model)
            p = param_specs(model, rules, mode=mode, dtype=jnp.bfloat16)
            cache = cache_specs(model, rules, shape.global_batch, shape.seq_len)
            b = decode_batch_specs(cfg, shape, rules)
            pos = sds((), jnp.int32, NamedSharding(mesh, P()))
            lowered = jax.jit(step, donate_argnums=(1,)).lower(p, cache, b, pos)
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax: one dict per device
        ca = ca[0] if ca else {}
    hlo = compiled.as_text()
    # XLA's cost_analysis counts while-loop bodies once; the HLO analyzer
    # multiplies by known trip counts (launch/hlo_cost.py).
    from repro.launch.hlo_cost import analyze as hlo_analyze

    cost = hlo_analyze(hlo)
    coll = dict(cost.collective_bytes)
    coll["total"] = cost.collective_total
    flops = cost.flops
    bytes_accessed = cost.bytes
    terms = roofline_terms(flops, bytes_accessed, coll["total"], V5E)
    mf = model_flops(cfg, shape)
    useful = mf / max(flops * n_chips, 1e-30)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "chips": n_chips,
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.temp_size_in_bytes
            + ma.output_size_in_bytes
            - ma.alias_size_in_bytes,
            "fits_hbm_16g": (
                ma.argument_size_in_bytes
                + ma.temp_size_in_bytes
                + ma.output_size_in_bytes
                - ma.alias_size_in_bytes
            )
            <= 16 * 2**30,
        },
        "cost": {
            "device_flops": flops,
            "device_bytes_accessed": bytes_accessed,
            "transcendentals": cost.transcendentals,
            # XLA's own (loop-body-once) numbers, for reference:
            "xla_flops": float(ca.get("flops", 0.0)),
            "xla_bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "roofline": terms,
        "model_flops": mf,
        "useful_flops_ratio": useful,
        "remat": remat,
        "zero1": zero1,
        "param_mode": mode,
        "num_microbatches": num_microbatches,
    }
    if save_hlo:
        Path(save_hlo).write_text(hlo)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument(
        "--multi-pod", choices=["single", "multi", "both"], default="single"
    )
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    from repro.configs import ARCHS, applicable_shapes, get_arch

    cells: list[tuple[str, str]] = []
    if args.all:
        for a, cfg in ARCHS.items():
            for s in applicable_shapes(cfg):
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape))
    meshes = {
        "single": [False], "multi": [True], "both": [False, True]
    }[args.multi_pod]

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
            path = out_dir / f"{tag}.json"
            if path.exists():
                print(f"[skip] {tag} (cached)", flush=True)
                continue
            print(f"[lower+compile] {tag} ...", flush=True)
            try:
                res = run_cell(
                    arch, shape, mp, zero1=args.zero1,
                    save_hlo=args.save_hlo and f"{args.save_hlo}/{tag}.hlo",
                )
                path.write_text(json.dumps(res, indent=1))
                r = res["roofline"]
                print(
                    f"  ok {res['compile_s']:.1f}s compile | "
                    f"peak/dev {res['memory']['peak_estimate_bytes']/2**30:.2f} GiB | "
                    f"terms c={r['compute_s']:.4f} m={r['memory_s']:.4f} "
                    f"n={r['collective_s']:.4f} -> {r['dominant']}",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append((tag, str(e)))
                print(f"  FAIL {tag}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, msg in failures:
            print(f"  {tag}: {msg[:200]}")
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
