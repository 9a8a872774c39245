"""Latency arithmetic: percentiles over every sample, never over medians of
chunks or blocks.  A copy of the percentile rule the served path's own
recorder uses (numpy's linear interpolation), kept with the benchmark."""
from __future__ import annotations

import numpy as np


class Recorder:
    """Append-only store of samples in milliseconds."""

    def __init__(self):
        self._chunks: list[np.ndarray] = []

    def record(self, samples_ms) -> None:
        a = np.atleast_1d(np.asarray(samples_ms, np.float64))
        if a.size:
            self._chunks.append(a)

    def samples(self) -> np.ndarray:
        if not self._chunks:
            return np.zeros((0,), np.float64)
        return np.concatenate(self._chunks)

    @property
    def count(self) -> int:
        return int(sum(c.size for c in self._chunks))

    def percentile(self, q: float) -> float | None:
        s = self.samples()
        return float(np.percentile(s, q)) if s.size else None
