"""Minimal structured logging + metric accumulation for training runs."""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field
from typing import Any


def log(msg: str, **kv: Any) -> None:
    parts = [f"[repro {time.strftime('%H:%M:%S')}] {msg}"]
    parts += [f"{k}={v}" for k, v in kv.items()]
    print(" ".join(parts), file=sys.stderr, flush=True)


@dataclass
class MetricLogger:
    """Accumulates scalar metric history."""

    history: dict[str, list[tuple[int, float]]] = field(default_factory=dict)

    def record(self, step: int, **metrics: float) -> None:
        for k, v in metrics.items():
            self.history.setdefault(k, []).append((int(step), float(v)))

    def last(self, key: str) -> float:
        return self.history[key][-1][1]

    def series(self, key: str) -> list[float]:
        return [v for _, v in self.history[key]]

    def best(self, key: str, mode: str = "max") -> float:
        vals = self.series(key)
        return max(vals) if mode == "max" else min(vals)
