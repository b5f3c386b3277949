"""The output check that decides ``correct``.

Once the window has closed and the program's device state is freed, a
sample of the finished requests, drawn from the seed, goes through the
float32 reference (``reference.py``), and these numbers are read, each
the worst over the sample unless named otherwise:

- ``logit_gap``: how far the reference's logit of the token the program
  served (the argmax of its logits) lies below the reference's best;
- ``logit_err``: the relative L2 distance of the served logits from the
  reference's, and ``logit_err_median``, its median over the sample;
- ``feat_err``: the relative L2 distance of the boundary pass's GAP
  feature from the reference's mean hidden state after the cut;
- ``sim_gap``: how far the reference's similarity of the center the
  boundary pass picked (``best``) lies below the reference's best, both
  against the centers that request was probed with.

A cell compares the numbers that ``checks/<workload>.json`` gives a limit;
the limits, the readings they were set from, and why a number is left
out of a cell are in ``PERF.md``.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("logit_gap", "logit_err", "logit_err_median", "feat_err",
           "sim_gap")


def sample_ids(finished, n: int, seed: int) -> list:
    ids = sorted(finished)
    rng = np.random.default_rng([int(seed), 0x636865636B])
    take = min(n, len(ids))
    return sorted(int(i) for i in rng.choice(ids, size=take, replace=False))


def sims(feat: np.ndarray, centers: np.ndarray) -> np.ndarray:
    """Eq. 8: cosine of the feature against each center, mapped to [0, 1]."""
    f = feat / max(np.linalg.norm(feat), 1e-12)
    c = centers / np.maximum(np.linalg.norm(centers, axis=1,
                                            keepdims=True), 1e-12)
    return (c @ f + 1.0) * 0.5


def per_request(served: list, ref_logits: np.ndarray, ref_feats: np.ndarray
                ) -> dict:
    """``served``: per sampled request ``{"logits", "feat", "centers",
    "best_index"}`` (``best_index`` into ``centers``); the reference's
    logits and features in the same order.  Returns each number's value
    for every request."""
    out = {k: [] for k in ("logit_gap", "logit_err", "feat_err", "sim_gap")}
    for s, rl, rf in zip(served, ref_logits, ref_feats):
        pl = np.asarray(s["logits"], np.float64)
        rl = np.asarray(rl, np.float64)
        rf = np.asarray(rf, np.float64)
        pf = np.asarray(s["feat"], np.float64)
        rs = sims(rf, np.asarray(s["centers"], np.float64))
        out["logit_gap"].append(rl.max() - rl[int(np.argmax(pl))])
        out["logit_err"].append(np.linalg.norm(pl - rl) / np.linalg.norm(rl))
        out["feat_err"].append(np.linalg.norm(pf - rf) / np.linalg.norm(rf))
        out["sim_gap"].append(rs.max() - rs[int(s["best_index"])])
    return {k: np.asarray(v) for k, v in out.items()}


def readings(served: list, ref_logits: np.ndarray, ref_feats: np.ndarray
             ) -> dict:
    """The worst of each number over the sample, and the median of the
    logits' distance (``logit_err_median``)."""
    per = per_request(served, ref_logits, ref_feats)
    out = {k: float(v.max()) for k, v in per.items()}
    out["logit_err_median"] = float(np.median(per["logit_err"]))
    return out


def judge(values: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]) for the numbers that have a limit;
    a number without a finite value fails."""
    rows = [(k, values[k], float(limits[k])) for k in NUMBERS if k in limits]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return bool(ok and rows), rows


def over_capacity(loads: np.ndarray, capacity: int) -> int:
    """Assignments beyond ``capacity`` slots per expert, summed over layers,
    prompts and experts: what a capacity dispatch would drop."""
    return int(np.maximum(loads - capacity, 0).sum())
