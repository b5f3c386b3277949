"""The benchmark's traffic: one general generator, driven by a mix's data
file (``traffic/<mix>.json``) and the run's seed.

``TaskStream`` is a copy of the program's ``CorrelatedTaskStream``
(``data/pipeline.py``) with the same draws in the same order: labels in
runs of about 1, 5 or 20 ("low" / "medium" / "high" correlation) from a
long-tailed label distribution, a scene offset per run that drifts, and
Gaussian class clusters.  ``tokens`` is ``launch/serve.py``'s mapping of a
task's features to token ids, taken over ``seq_len`` features instead of
8.  Keeping copies here means a later change to the program's generator
leaves this yardstick alone.

``arrival_offsets`` gives an open loop's due times: one Poisson sample
path of exponential gaps, drawn once from a fixed seed, replayed from a
point that the run's seed picks (a rotation of the same gaps).  The
offered work and the number of requests are the same in every run; a
free shuffle of the gaps instead moved the 95th percentile by 7-23%
(IQR over 12 seeds, simulated at 0.5-0.8 of the knee) where a rotation
moves it by about 1%.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

GAP_SEED = 20250117  # fixes the set of open-loop gaps shared by all seeds


@dataclasses.dataclass
class Task:
    id: int
    label: int
    features: np.ndarray


class TaskStream:
    RUN = {"low": 1, "medium": 5, "high": 20}

    def __init__(self, n_labels: int, dim: int, correlation: str,
                 seed: int, label_skew: float = 1.2, drift: float = 0.1):
        rng = np.random.default_rng(seed)
        self.rng = rng
        self.n_labels = n_labels
        self.dim = dim
        self.mu = rng.normal(size=(n_labels, dim)) * 1.0
        self.sigma = rng.uniform(1.5, 3.5, size=n_labels)
        self.drift = drift
        self.run = self.RUN[correlation]
        w = 1.0 / np.arange(1, n_labels + 1) ** label_skew
        self.label_p = w / w.sum()
        self._cur_label: Optional[int] = None
        self._left = 0
        self._id = 0

    def _next_label(self) -> int:
        if self._left <= 0:
            self._cur_label = int(self.rng.choice(self.n_labels,
                                                  p=self.label_p))
            self._left = max(1, int(self.rng.poisson(self.run)))
            self._scene = (self.rng.normal(size=self.dim)
                           * self.sigma[self._cur_label])
        self._left -= 1
        return self._cur_label

    def next_task(self) -> Task:
        j = self._next_label()
        self._scene += self.rng.normal(size=self.dim) * self.drift
        disp = self._scene + self.rng.normal(size=self.dim) * 0.3 * self.sigma[j]
        t = Task(self._id, j, (self.mu[j] + disp).astype(np.float32))
        self._id += 1
        return t

    def tasks(self, n: int) -> List[Task]:
        return [self.next_task() for _ in range(n)]


def tokens(task: Task, seq_len: int, vocab: int) -> np.ndarray:
    """(1, seq_len) int32 token ids from the task's first ``seq_len``
    features, as ``serve()`` maps its first 8."""
    if seq_len > task.features.shape[0]:
        raise ValueError(f"seq_len {seq_len} > feature dim "
                         f"{task.features.shape[0]}")
    toks = (np.abs((task.features[:seq_len] * 1000).astype(np.int64))
            % vocab).astype(np.int32)
    return toks[None]


def arrival_offsets(rate_rps: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in ``[0, seconds)`` of a Poisson process at ``rate_rps``:
    the gaps drawn from ``GAP_SEED`` (so their count and their sum are the
    same for every seed), rotated to start where ``seed`` says."""
    rng = np.random.default_rng(GAP_SEED)
    n = int(rate_rps * seconds)
    gaps = rng.exponential(1.0 / rate_rps, size=n)
    # scale so the n gaps end exactly at the window's close: every seed
    # offers n requests over the same span
    gaps *= seconds / gaps.sum()
    gaps = np.roll(gaps, -int(np.random.default_rng(seed).integers(n)))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
