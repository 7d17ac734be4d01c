"""Roofline accounting from compiled SPMD artifacts.

``cost_analysis()`` (flops / bytes) is per-device after partitioning
(verified empirically: a 512-way sharded matmul reports total/512 flops).
Collective traffic is not in cost_analysis, so we parse the compiled HLO and
sum *operand* bytes of every collective op — shapes in the partitioned
module are already per-device.

Hardware model (TPU v5e, per brief): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.  collective term = per-device collective bytes / link
bandwidth (each chip drives its links at the payload rate; ring all-reduce
moves 2x the shard but overlaps both directions — we report raw
payload/bandwidth and call out the model in EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# post-optimization HLO prints operands without types, so we meter the
# RESULT type: `%x = f32[256,4096]{1,0} all-reduce(%y), ...` or a tuple for
# variadic/-start forms.  Result bytes == payload for all-reduce/permute,
# == received bytes for all-gather; reduce-scatter's wire bytes are ~result
# x group size (we report result bytes — a lower bound, stated in
# EXPERIMENTS.md).  `-done` ops are skipped (their start was counted).
_OP_RE = re.compile(
    r"=\s+(?P<type>\([^=]*?\)|\S+)\s+"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind result bytes (per device) + op counts."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        if m.group("suffix") == "-done":
            continue
        kind = m.group("kind")
        nb = sum(_shape_bytes(d, s)
                 for d, s in _SHAPE_RE.findall(m.group("type")))
        out[kind] += nb
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


@dataclasses.dataclass
class Roofline:
    flops: float                # per device
    hbm_bytes: float            # per device
    coll_bytes: float           # per device
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float          # per device ("useful" flops)
    useful_ratio: float

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline(cost: dict, coll: dict, model_flops_per_device: float
             ) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    cb = float(coll["total_bytes"])
    terms = {"compute": flops / PEAK_FLOPS,
             "memory": hbm / HBM_BW,
             "collective": cb / ICI_BW}
    dominant = max(terms, key=terms.get)
    return Roofline(
        flops=flops, hbm_bytes=hbm, coll_bytes=cb,
        compute_s=terms["compute"], memory_s=terms["memory"],
        collective_s=terms["collective"], dominant=dominant,
        model_flops=model_flops_per_device,
        useful_ratio=(model_flops_per_device / flops) if flops else 0.0)


def model_flops_per_step(cfg, shape) -> float:
    """6*N*D train / 2*N*D forward, N = active params (global, whole step)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch      # decode: one token/seq
