"""What a driver hands back to the harness."""

from __future__ import annotations

import math
from dataclasses import dataclass, field


@dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """A run's end-to-end metrics, its checks, and what the per-layer
    readers read (``layer``)."""

    metrics: dict  # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: list  # [Check]
    memory_peak_bytes: int
    window_s: float
    layer: dict = field(default_factory=dict)
