"""The one traffic generator: reads a mix's data file and makes requests.

A mix (``traffic/<name>.json``) names its arrival process and its length
laws, and may give a set of shared prefixes.  Each process and each law
is a file of its own, found by name:

    traffic/arrivals/<arrival>.py   ``source(traffic, workload, lead_s,
                                    window_s)``: what to send, and when
    traffic/lengths/<dist>.py       ``inverse_cdf(dist, u)``

so a new kind of traffic is new files.  The cell (``workloads/<cell>.json``)
gives the rate, the number of clients and the like.

Every seed gets the same schedule: lengths are the law's quantiles at
``(i + 0.5) / n`` and gaps the arrival law's, paired and ordered by a
fixed shuffle that depends on the mix alone; the seed draws the token ids
(and, in the harness, the weights).  With a dozen requests in a window,
the order of arrivals decides the queueing: a seed that reordered them
would change the work, and the spread between runs would be the draw's,
not the system's.
"""
from __future__ import annotations

import importlib.util
import math
import os
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")


def plugin(kind: str, name: str, here: str = HERE):
    """The module ``traffic/<kind>/<name>.py``."""
    path = os.path.join(here, kind, name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"no {kind} law {name!r}: add {path}")
    spec = importlib.util.spec_from_file_location(
        f"serve_bench_{kind}_" + "".join(
            c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Req:
    """One generated request: ``due`` seconds after the window opens
    (negative in the lead-in; None for closed-loop requests, which are
    sent when a client frees up)."""
    prompt: np.ndarray
    max_new: int
    due: Optional[float] = None
    prefix: int = -1                    # shared prefix index, -1 for none


def quantiles(dist: dict, n: int, here: str = HERE) -> np.ndarray:
    """``n`` integer lengths at the law's mid-quantiles, clipped."""
    u = (np.arange(n) + 0.5) / n
    x = plugin("lengths", dist["dist"], here).inverse_cdf(dist, u)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def zipf_counts(k: int, s: float, n: int) -> np.ndarray:
    """Split ``n`` requests over ``k`` items with Zipf(s) popularity,
    largest remainders first, so the counts always sum to ``n``."""
    w = 1.0 / np.arange(1, k + 1) ** s
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    for i in np.argsort(-(share - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts


class Traffic:
    """Requests of one mix for one seed.  ``max_seq`` bounds every request:
    a prompt plus its outputs never reaches the engine's clamp."""

    def __init__(self, mix: dict, seed: int, vocab: int, max_seq: int,
                 here: str = HERE):
        self.mix = mix
        self.here = here
        self.vocab = vocab
        self.max_seq = max_seq
        self.rng = np.random.default_rng(seed)        # token ids only
        pre = mix.get("prefix")
        self.prefixes = ([self.rng.integers(0, vocab, pre["tokens"])
                          for _ in range(pre["count"])] if pre else [])
        longest = (pre["tokens"] if pre else 0) + mix["prompt"]["max"] \
            + mix["output"]["max"]
        if longest > max_seq - 1:
            raise ValueError(f"a request of this mix may hold {longest} "
                             f"tokens; the engine serves at most "
                             f"{max_seq - 1}")

    def batch(self, n: int) -> List[Req]:
        """``n`` requests: (prompt length, output length, prefix) triples
        paired and ordered the same way for every seed, with token ids
        drawn from the seed."""
        fixed = np.random.default_rng(n)
        plen = quantiles(self.mix["prompt"], n, self.here)
        olen = fixed.permutation(quantiles(self.mix["output"], n, self.here))
        pre = self.mix.get("prefix")
        which = (fixed.permutation(np.repeat(
            np.arange(pre["count"]), zipf_counts(pre["count"], pre["zipf"], n)))
            if pre else np.full(n, -1))
        out = []
        for i in fixed.permutation(n):
            toks = self.rng.integers(0, self.vocab, int(plen[i]))
            if which[i] >= 0:
                toks = np.concatenate([self.prefixes[which[i]], toks])
            out.append(Req(toks.astype(np.int32), int(olen[i]),
                           prefix=int(which[i])))
        return out

    def open_loop(self, rate: float, lead_s: float, window_s: float,
                  gaps: Callable[[int], np.ndarray],
                  preload: int = 0) -> List[Req]:
        """Arrivals at ``rate`` over the lead-in and the window, each
        segment with its own fixed multiset of ``gaps(n)`` (a law of mean
        1, rescaled to fill the segment), so the window always holds
        ``round(rate * window_s)`` requests.  ``preload`` more arrive at
        the start of the lead-in, so that the engine holds about as many
        requests as in steady state when the window opens."""
        reqs = self.batch(preload) if preload else []
        for r in reqs:
            r.due = -lead_s
        for t0, span in ((-lead_s, lead_s), (0.0, window_s)):
            n = int(round(rate * span))
            if n == 0:
                continue
            g = np.random.default_rng(n).permutation(gaps(n))
            g = g * (span / g.sum())
            due = t0 + np.concatenate([[0.0], np.cumsum(g)[:-1]])
            for r, d in zip(self.batch(n), due):
                r.due = float(d)
                reqs.append(r)
        return reqs

    def source(self, workload: dict, lead_s: float, window_s: float):
        """The mix's arrival process for this cell (``arrivals/<name>.py``):
        an object with ``closed`` (the run ends at the window's close),
        ``take(now)`` -> [(request, due)] to send now, ``next_due()`` (or
        None) and ``finished(request, tokens, now)``; times in seconds from
        the window's opening."""
        return plugin("arrivals", self.mix["arrival"], self.here).source(
            self, workload, lead_s, window_s)


class Schedule:
    """Open loop: requests sent at their due times, whatever the engine is
    doing."""
    closed = False

    def __init__(self, reqs: List[Req]):
        self.reqs = sorted(reqs, key=lambda r: r.due)
        self.i = 0

    def take(self, now: float):
        out = []
        while self.i < len(self.reqs) and self.reqs[self.i].due <= now:
            out.append((self.reqs[self.i], self.reqs[self.i].due))
            self.i += 1
        return out

    def next_due(self) -> Optional[float]:
        return self.reqs[self.i].due if self.i < len(self.reqs) else None

    def finished(self, req, tokens, now: float) -> None:
        pass


def boundary_lengths(dist: dict, marks) -> List[int]:
    """Prompt lengths that reach every compiled shape the mix can: the
    ends of the range and both sides of each mark (bucket sizes, page
    counts) inside it."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = {lo, hi}
    for m in marks:
        for x in (m, m + 1):
            if lo <= x <= hi:
                out.add(int(x))
    return sorted(out)


def page_marks(block_size: int, max_seq: int) -> List[int]:
    """Token counts at which a power-of-two page count is full."""
    return [block_size * 2 ** k
            for k in range(int(math.log2(max(1, max_seq // block_size))) + 2)]
