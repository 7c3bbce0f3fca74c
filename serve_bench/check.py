"""Decides ``correct``: served tokens against the plain float32 reference.

Once the window has closed and the engine is freed, a sample of the
requests that served tokens (finished or not: a long request may outlive
the run, and each greedy token is final once served), drawn from the seed
and always holding the one with the most served tokens, is run through
``reference.py`` over its prompt and served tokens.  At each served
position the reference's best logit and its logit of the served token are
read; their difference is the gap.  A
greedy token that the reference also puts first reads 0; rounding in bf16
lets a near-tie flip, which reads a small gap; a wrong computation reads a
large one.  Two numbers are read from the gaps: the widest (``max_gap``)
and the mean over every served position (``mean_gap``).

The cell's ``check.limits`` (``workloads/<cell>.json``) says which of them
it compares and the limit of each; PERF.md gives the readings each limit
was set from.

The control goes through the same comparison: at the same positions, the
token that the reference computed in int8 (W8A8) puts first stands in for
the served token, and the cell's limits have to find it not correct.
"""
from __future__ import annotations

import numpy as np

from serve_bench import reference, weights


def sample(cell, seed: int, served) -> list:
    """Requests to check: the one with the most served tokens, then others
    drawn from the seed, up to the cell's ``check.requests``."""
    served = sorted(served, key=lambda r: r.rid)
    if not served:
        return []
    k = int(cell.workload["check"]["requests"])
    longest = max(served, key=lambda r: (len(r.out), len(r.req.prompt)))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(weights.jax_seed(seed, salt=1))
    pick = rng.permutation(len(rest))[: max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def positions(rec):
    """(tokens, targets, first served position) of one served request."""
    p, out = np.asarray(rec.req.prompt, np.int32), np.asarray(rec.out, np.int32)
    toks = np.concatenate([p, out[:-1]])
    tgts = np.concatenate([p[1:], out])
    return toks, tgts, len(p) - 1


def served_gaps(cfg, params, recs, control: bool = False) -> np.ndarray:
    """Reference best logit minus reference logit of the served token, at
    every served position of ``recs``; with ``control``, of the token the
    int8 reference puts first there instead."""
    gaps = []
    for r in recs:
        toks, tgts, first = positions(r)
        if control:
            _, _, tgts = reference.score(cfg, params, toks, tgts, int8=True)
        best, got, _ = reference.score(cfg, params, toks, tgts)
        gaps.append((best - got)[first:])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def readings(gaps: np.ndarray) -> dict:
    """The numbers a run can compare: the widest gap and the mean gap over
    every served position of the sample (1e30 where there is no finite
    reading)."""
    if gaps.size == 0 or not np.isfinite(gaps).all():
        return {"max_gap": 1e30, "mean_gap": 1e30}
    return {"max_gap": float(gaps.max()), "mean_gap": float(gaps.mean())}


def compare(cell, seed: int, served, params, control: bool = False):
    """(correct, {name: (value, limit)}, all readings, tokens compared)
    for one run; the cell's ``check.limits`` names the numbers compared.
    ``control`` puts the int8 reference in the program's place."""
    limits = cell.workload["check"]["limits"]
    gaps = served_gaps(cell.config, params, sample(cell, seed, served),
                       control)
    vals = readings(gaps)
    compared = {k: (vals[k], float(lim)) for k, lim in limits.items()}
    ok = all(v <= lim for v, lim in compared.values())
    return ok, compared, vals, int(gaps.size)
