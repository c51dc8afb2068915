"""The step megakernel's logical work, and the chip's peaks.

The work is what the algorithm must move and compute for one session and
one tick, from ``(m, n, P)`` and the storage dtype alone, never from the
program's padded layout: a layout that pads less reads the same logical
work, and its roofline share rises.

Bytes: read X (P·m), the weight row W (P), B (n·m) and Ĥ (n·n); write B′,
Ĥ′, Y (P·n), and the per-session conv (f32), health word (int32) and
moments [Σy², Σy⁴] (2 f32).

FLOPs (multiply and add counted apart): Y = X Bᵀ (2·P·m·n); the two
weighted Gram products Yᵀ W Y and Gᵀ W Y (2·2·P·n·n); the commit Ĥ′B
(2·n·n·m).  Elementwise work (the nonlinearity, the weighting, the norms)
is left out: it is a lower bound.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

ITEMSIZE = {"float32": 4, "bfloat16": 2}
PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def step_bytes_per_session(m: int, n: int, P: int, dtype: str = "float32") -> int:
    s = ITEMSIZE[dtype]
    reads = P * m * 4 + P * 4 + (n * m + n * n) * s
    writes = (n * m + n * n) * s + P * n * 4 + 4 + 4 + 2 * 4
    return reads + writes


def step_flops_per_session(m: int, n: int, P: int) -> int:
    return 2 * P * m * n + 2 * 2 * P * n * n + 2 * n * n * m


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]


def least_time_s(config: Dict, sessions: float, device_kind: str) -> Dict:
    """The least time one tick of ``sessions`` sessions could take on one
    chip, and which bound sets it."""
    m, n, P = int(config["m"]), int(config["n"]), int(config["P"])
    pk = peaks(device_kind)
    t_bytes = sessions * step_bytes_per_session(m, n, P, config["dtype"]) / pk["hbm_bytes_per_s"]
    t_flops = sessions * step_flops_per_session(m, n, P) / pk["matmul_flops_per_s"]
    return {
        "seconds": max(t_bytes, t_flops),
        "bound": "bytes" if t_bytes >= t_flops else "flops",
    }
