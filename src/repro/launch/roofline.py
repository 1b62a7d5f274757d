"""Roofline-term extraction from compiled dry-run artifacts.

Peaks come from a table keyed by ``jax.Device.device_kind``; a kind not in
the table raises instead of borrowing another chip's numbers.  The
dry-runs target TPU v5e (``"TPU v5 lite"``): 197 TFLOP/s bf16 per chip,
819 GB/s HBM, ~50 GB/s/link ICI.  The compiled program produced by the SPMD partitioner is the
*per-chip* program, so cost_analysis() FLOPs/bytes and the collective
operand sizes parsed from the optimized HLO are per-chip quantities:

  compute term    = flops_per_chip / PEAK_FLOPS
  memory term     = bytes_per_chip / HBM_BW
  collective term = collective_bytes_per_chip / LINK_BW
                    (== total_collective_bytes / (chips x link_bw))

Per-op traffic convention: bytes of the op *result* (per-chip shapes),
doubled for all-reduce (reduce + broadcast phases of a ring).  Async
``-start``/``-done`` pairs are counted once.
"""
from __future__ import annotations

import re
from typing import Dict, List

# per-chip peaks (Google Cloud documentation, "TPU v5e"): bf16 FLOP/s,
# HBM bytes/s, ICI bytes/s per link
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"     # the chip the dry-runs model


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_OP_RE = re.compile(
    r"=\s+(?P<result>\(.*?\)|\S+?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<async>-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(result: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(result):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-kind {count, bytes} from optimized (post-SPMD) HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        if m.group("async") == "-done":
            continue  # paired with its -start
        kind = m.group("op")
        b = _shape_bytes(m.group("result"))
        if kind == "all-reduce":
            b *= 2  # reduce + broadcast phases
        out[kind]["count"] += 1
        out[kind]["bytes"] += b
    return out


def collective_bytes(hlo_text: str) -> int:
    return int(sum(v["bytes"] for v in parse_collectives(hlo_text).values()))


def roofline_terms(flops_per_chip: float, bytes_per_chip: float,
                   coll_bytes_per_chip: float,
                   device_kind: str = DRYRUN_DEVICE_KIND) -> Dict[str, float]:
    p = peaks(device_kind)
    terms = {
        "compute_s": flops_per_chip / p["flops"],
        "memory_s": bytes_per_chip / p["hbm_bw"],
        "collective_s": coll_bytes_per_chip / p["link_bw"],
    }
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    terms["dominant"] = dom.replace("_s", "")
    # roofline fraction: how much of the bound is the useful compute term
    terms["roofline_fraction"] = (terms["compute_s"] / bound
                                  if bound > 0 else 0.0)
    return terms


def model_flops(kind: str, n_params_active: int, tokens: int) -> float:
    """6ND for training (fwd+bwd), 2ND for inference passes."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * tokens


def format_table(rows: List[Dict], keys: List[str]) -> str:
    widths = [max(len(k), *(len(str(r.get(k, ""))) for r in rows))
              for k in keys]
    lines = [" | ".join(k.ljust(w) for k, w in zip(keys, widths)),
             "-|-".join("-" * w for w in widths)]
    for r in rows:
        lines.append(" | ".join(str(r.get(k, "")).ljust(w)
                                for k, w in zip(keys, widths)))
    return "\n".join(lines)
