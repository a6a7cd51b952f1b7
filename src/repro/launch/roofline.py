"""Roofline terms from compiled dry-run artifacts (DESIGN.md §6).

Peaks come from :data:`PEAKS`, one entry per ``device_kind``; a kind that
is not in it is an error, never a default. Terms per (arch × shape × mesh):
    compute    = HLO_FLOPs   / (chips * peak FLOP/s)
    memory     = HLO_bytes   / (chips * HBM bytes/s)
    collective = coll_bytes  / (chips * ICI bytes/s per link)

``collective_bytes`` parses the optimized HLO text and sums operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops (cost_analysis does not report them).
"""
from __future__ import annotations

import re
from typing import Dict

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# "TPU v5 lite" is TPU v5e. Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of ICI (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# the chip the dry-run's production meshes stand for (DESIGN.md §5)
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; raises on an unknown kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to roofline.PEAKS with their source")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

# e.g.  %all-reduce.5 = f32[1024,512]{1,0} all-reduce(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\s(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)[\.\s(]"
)
# tuple-shaped collectives:  = (f32[8,4]{..}, f32[16]{..}) all-reduce(
_TUPLE_RE = re.compile(
    r"=\s*\(((?:[a-z0-9]+\[[0-9,]*\][^,()]*,?\s*)+)\)\s*(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)[\.\s(]"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum of result-shape bytes per collective kind (proxy for bytes moved)."""
    out = {k: 0.0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if not any(c in line for c in _COLLECTIVES):
            continue
        m = _OP_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            out[kind] += _shape_bytes(dtype, dims)
            count[kind] += 1
            continue
        m = _TUPLE_RE.search(line)
        if m:
            shapes, kind = m.groups()
            for dm in _SHAPE_RE.finditer(shapes):
                out[kind] += _shape_bytes(*dm.groups())
            count[kind] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count  # type: ignore[assignment]
    return out


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   coll_bytes_per_device: float, *, device_kind: str) -> dict:
    """Terms in seconds from PER-DEVICE totals (the compiled module is the
    per-device SPMD program; global = per-device totals balanced across chips,
    so per-device/peak IS the global step-time bound per term)."""
    pk = peaks(device_kind)
    compute = flops_per_device / pk["flops"]
    memory = bytes_per_device / pk["hbm_bw"]
    collective = coll_bytes_per_device / pk["ici_bw"]
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    dominant = max(terms, key=terms.get)
    terms["dominant"] = dominant
    terms["bound_s"] = terms[dominant]
    return terms


def model_flops(cfg, kind: str, global_batch: int, seq: int) -> float:
    """Analytic MODEL_FLOPS: 6*N*D for training, 2*N*D for inference
    (N = active params, D = tokens processed this step)."""
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = global_batch * seq
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = global_batch * seq
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * global_batch
