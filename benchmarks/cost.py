"""What the algorithm needs, from the shapes alone: bytes moved to and from
device memory and operations, for one fused cycle and one delta scatter.

Counted from the problem (P pools, T task rows, H hosts, U users, C the
considerable cap), whatever implements it, so a later kernel PR cannot make
the count stale.  Every input is read once and every output written once;
the greedy match's running host state (H x 4 floats) fits on chip and is
not counted as memory traffic.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def fused_cycle_cost(P: int, T: int, H: int, U: int, C: int) -> Dict:
    # per task row: base row id (4 B) + flags (1 B), and the gather of its
    # resources (cpus, mem, gpus, count: 16 B) and disk (4 B)
    in_bytes = P * T * (4 + 1 + 16 + 4)
    in_bytes += P * U * (3 + 4 + 1) * 4          # shares, quota, tokens
    in_bytes += P * H * (2 * 4 * 4 + 2)          # avail, capacity, 2 masks
    # outputs: the ranked queue rows, three [C] candidate columns, a count
    out_bytes = P * (T * 4 + 3 * C * 4 + 4)
    # rank: ~14 flops per row (cumsums, two divisions, a max) and a sort
    # of T keys; match: C steps over H hosts at ~12 flops (4 compares, the
    # fitness, the argmax)
    ops = P * (14 * T + T * max(math.log2(max(T, 2)), 1.0) + 12 * C * H)
    return {"bytes": float(in_bytes + out_bytes), "ops": float(ops)}


def delta_scatter_cost(K: int) -> Dict:
    """K changed rows: read (index, row id, flags), write (row id, flags)."""
    return {"bytes": float(K * (4 + 4 + 1 + 4 + 1)), "ops": float(K)}


def least_seconds(cost: Dict, device_kind: str) -> Dict:
    """The least time the chip could take, and which peak bounds it."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    pk = peaks[device_kind]
    t_mem = cost["bytes"] / pk["hbm_bytes_per_s"]
    t_ops = cost["ops"] / pk["flops_per_s"]
    return {"seconds": max(t_mem, t_ops),
            "bound": "memory" if t_mem >= t_ops else "compute"}
