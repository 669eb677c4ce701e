"""What a driver hands back to ``run.py``."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List


@dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]                  # metric name -> value
    counters: Dict[str, float] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)  # printed on earlier lines
