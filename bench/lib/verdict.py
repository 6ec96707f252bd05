"""The verdict ``correct``: every number compared is finite and at most
its limit (``bench/limits/<cell>.json``), and no unit of work failed."""
from __future__ import annotations

import math


def judge(readings: dict, limits: dict, failed: int = 0) -> tuple:
    """-> (correct, {name: {"value", "limit"}}). A reading without a
    limit, or a limit without a reading, is a fault of the benchmark."""
    if set(readings) != set(limits):
        raise KeyError(f"readings {sorted(readings)} against limits "
                       f"{sorted(limits)}")
    checks = {k: {"value": float(v), "limit": limits[k]}
              for k, v in readings.items()}
    correct = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return correct, checks
