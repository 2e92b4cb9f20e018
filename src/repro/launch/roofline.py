"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch x shape x mesh) cell, from the compiled per-device
HLO module (cost_analysis / memory_analysis are per-device on this path):

  compute term    = device_FLOPs / peak_FLOPs_per_chip
  memory term     = device_bytes_accessed / HBM_bw_per_chip
  collective term = device_collective_bytes / ICI_link_bw

cost_analysis does not expose collective traffic, so collective bytes are
parsed from the post-SPMD HLO text: for every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute op we sum the output
operand bytes (all-reduce counted twice — ring RS+AG moves ~2x the payload).
Hardware constants come from `PEAKS`, keyed by the device kind JAX
reports; a device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import re

__all__ = [
    "PEAKS",
    "V5E",
    "Hardware",
    "hardware",
    "collective_bytes",
    "roofline_terms",
    "roofline_fraction",
    "model_flops",
]


@dataclasses.dataclass(frozen=True)
class Hardware:
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per link


#: Published per-chip peaks, keyed by `jax.Device.device_kind`.
#: TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect
#: (four links of ~50 GB/s).
PEAKS = {
    "TPU v5 lite": Hardware(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def hardware(device_kind: str) -> Hardware:
    """Peaks of one device kind; unknown kinds raise `ValueError`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            "to repro.launch.roofline.PEAKS with their source"
        ) from None


#: The chip the dry-run's described pods are made of.
V5E = PEAKS["TPU v5 lite"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_SHAPE_RE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128)\[([0-9,]*)\]")
_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device bytes moved by collectives, keyed by op kind (+ 'total').

    Parses instruction lines `%name = <out shapes> <op>(...)`; output shapes
    are summed per op (tuples included).  all-reduce weighted 2x.
    """
    out = {k: 0.0 for k in _COLL_OPS}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        op = None
        for cand in _COLL_OPS:
            # match "all-reduce(" / "all-gather-start(" etc.
            if re.search(rf"\b{cand}(-start|-done)?\(", rhs):
                op = cand
                break
        if op is None:
            continue
        if re.search(rf"\b{op}-done\(", rhs):
            continue  # avoid double counting start/done pairs
        head = rhs.split("(", 1)[0]
        nbytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))
        if op == "all-reduce":
            nbytes *= 2
        out[op] += float(nbytes)
    out["total"] = sum(out[k] for k in _COLL_OPS)
    return out


def roofline_terms(
    device_flops: float,
    device_bytes: float,
    device_collective_bytes: float,
    hw: Hardware,
) -> dict[str, float]:
    compute = device_flops / hw.peak_flops
    memory = device_bytes / hw.hbm_bw
    collective = device_collective_bytes / hw.ici_bw
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    dominant = max(terms, key=terms.get)
    total = max(terms.values())
    terms["dominant"] = dominant
    terms["bound_s"] = total
    return terms


def roofline_fraction(bound_s: float, measured_s: float) -> float:
    """Achieved fraction of the roofline bound: 1.0 means the measured
    time equals the model's hardware limit; small values mean the program
    sits far under the roofline (overhead/latency bound, as a serial
    event calendar on a host CPU is).  0.0 when nothing was measured."""
    if measured_s <= 0:
        return 0.0
    return bound_s / measured_s


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D with N = active params (MoE: routed active only),
    D = tokens processed.  Decode steps process global_batch tokens."""
    from repro.models.model import build_model
    import jax

    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    if cfg.num_experts:
        # Replace full expert stack by the activated fraction.  Expert
        # leaves are (E, D, F) per layer or (reps, E, D, F) scan-stacked.
        flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        expert_params = sum(
            leaf.size
            for kp, leaf in flat
            if leaf.ndim in (3, 4)
            and cfg.num_experts in leaf.shape
            and any(
                str(getattr(k, "key", "")) in ("w_gate", "w_up", "w_down")
                for k in kp
            )
        )
        active = total - expert_params + expert_params * (
            cfg.top_k / cfg.num_experts
        )
    else:
        active = total
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    tokens = shape.global_batch  # one new token per sequence
    return 2.0 * active * tokens
