"""Post-SPMD HLO analysis: collective bytes, FLOPs, memory — the inputs to
the roofline terms (EXPERIMENTS.md §Roofline).

The compiled module is the *per-device* program (GSPMD partitioned), so all
quantities extracted here are per-device; the roofline terms divide by
per-chip peaks directly.
"""
from __future__ import annotations

import math
import re
from typing import Dict

from repro.launch import hw

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# `%x = f32[8,128]{1,0} all-gather(...)` or async `all-gather-start(...)`;
# tuple results enumerate every dtype[shape] group before the op name.
_LINE_RE = re.compile(
    r"=\s+(?P<shapes>\(?[a-z0-9\[\],{}\s:#*()]+?\)?)\s+"
    r"(?P<op>" + "|".join(COLLECTIVE_OPS) + r")(?P<suffix>-start)?\(",
)
_SHAPE_RE = re.compile(r"(?P<dtype>[a-z]\d*[a-z0-9]*)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shapes_str):
        dt = m.group("dtype")
        if dt not in hw.BYTES:
            continue
        dims = m.group("dims")
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * hw.BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, dict]:
    """Per-op-kind {count, bytes} from the post-optimization HLO text.
    Bytes = result-shape bytes per device (one traversal of the data)."""
    stats = {op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        stats[op]["count"] += 1
        stats[op]["bytes"] += _shape_bytes(m.group("shapes"))
    stats["total_bytes"] = sum(
        v["bytes"] for k, v in stats.items() if isinstance(v, dict))
    stats["total_count"] = sum(
        v["count"] for k, v in stats.items() if isinstance(v, dict))
    return stats


def roofline_terms(flops_per_dev: float, hbm_bytes_per_dev: float,
                   coll_bytes_per_dev: float, device_kind: str) -> dict:
    """Lower-bound seconds per term against ``device_kind``'s declared
    peaks (``hw.peaks`` raises for an undeclared device)."""
    pk = hw.peaks(device_kind)
    compute_s = flops_per_dev / pk.flops_bf16
    memory_s = hbm_bytes_per_dev / pk.hbm_bw
    collective_s = coll_bytes_per_dev / pk.ici_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom.removesuffix("_s")
    bound = max(compute_s, memory_s, collective_s)
    terms["bound_s"] = bound
    terms["compute_fraction"] = compute_s / bound if bound else 0.0
    return terms


def model_flops(n_params_active: int, tokens: int, *,
                backward: bool) -> float:
    """6*N*D (training) or 2*N*D (inference) useful model FLOPs."""
    per_tok = 6 * n_params_active if backward else 2 * n_params_active
    return float(per_tok) * float(tokens)
