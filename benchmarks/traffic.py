"""The one traffic generator: a traffic file's parameters -> inputs.

A traffic mix is a JSON file under ``benchmarks/traffic/`` with a
``kind``:

- ``train``: a pool of token batches for the trainer's step;
- ``backlog``: a closed backlog of requests that keeps every slot full;
- ``openloop``: requests that fall due on the wall clock.

The work in a run does not depend on the seed, only its order does.  A
file fixes the MULTISET of (prompt length, output length) pairs — each
stated distribution evaluated at fixed, evenly spaced quantiles, the two
paired by a permutation the file fixes — and the seed permutes the
requests and draws the token ids (Zipf over the unpadded vocabulary, as
the trainer's own synthetic stream).  Open-loop arrivals are a Poisson
process conditioned on its count and on its gaps: exactly
``round(rate * seconds)`` due times whose inter-arrival gaps are the
exponential distribution at fixed quantiles, ordered by the seed.

numpy only; nothing here touches jax or the program.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for ``--seed`` (any whole number; the driver's are
    larger than 2**31) and a stream id of this module's choosing."""
    return np.random.default_rng([abs(int(seed)), int(stream)])


# ----------------------------------------------------------------- tokens
_ZIPF_CDF: Dict[int, np.ndarray] = {}


def zipf_tokens(rng: np.random.Generator, vocab: int, shape) -> np.ndarray:
    """Token ids with p proportional to 1/rank over the UNPADDED
    vocabulary (a uniform stream sits at its entropy floor, so a loss
    could not fall on it)."""
    cdf = _ZIPF_CDF.get(vocab)
    if cdf is None:
        p = 1.0 / np.arange(1, vocab + 1)
        cdf = _ZIPF_CDF[vocab] = np.cumsum(p / p.sum())
    ids = np.searchsorted(cdf, rng.random(shape), side="right")
    return np.minimum(ids, vocab - 1).astype(np.int32)


# ---------------------------------------------------------------- lengths
def quantile_values(dist: dict, n: int) -> List[int]:
    """``dist`` at the ``n`` quantiles (i + 0.5) / n, as whole numbers
    clipped to [lo, hi].  ``loguniform`` between lo and hi, or
    ``lognormal`` with a median and a sigma (of the log)."""
    lo, hi = int(dist["lo"]), int(dist["hi"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "loguniform":
            x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
        elif dist["dist"] == "lognormal":
            z = statistics.NormalDist().inv_cdf(u)
            x = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(int(min(max(round(x), lo), hi)))
    return out


def length_multiset(traffic: dict, n: int) -> List[Tuple[int, int]]:
    """The ``n`` (prompt, output) pairs the file fixes: both
    distributions at ``n`` quantiles, paired by the permutation
    ``pairing_seed`` gives — the same whatever ``--seed`` is."""
    prompts = quantile_values(traffic["prompt"], n)
    outputs = quantile_values(traffic["output"], n)
    pairing = np.random.default_rng(
        int(traffic.get("pairing_seed", 0))).permutation(n)
    pairs = [(prompts[i], outputs[int(pairing[i])]) for i in range(n)]
    limit = int(traffic["max_total_len"])
    for p, o in pairs:
        if p + o > limit:
            raise ValueError(f"prompt {p} + output {o} exceeds {limit}")
    return pairs


@dataclasses.dataclass
class PlannedRequest:
    """One request as the generator plans it; the runner turns it into
    the program's ``Request``.  ``prompt`` already holds the pre-aged
    tokens, ``new_tokens`` is what is still to be generated."""

    uid: int
    prompt: np.ndarray
    new_tokens: int
    due_s: float = 0.0          # open loop: seconds after the ramp starts
    measured: bool = True       # open loop: due inside the window
    aged_tokens: int = 0        # backlog: output delivered as prompt


# ---------------------------------------------------------------- backlog
class Backlog:
    """Generations of ``traffic['generation']`` requests, each a seeded
    permutation of the same multiset.  The first is pre-aged so that the
    window opens in the stationary regime: request ``i`` of the multiset
    has a stratified share ``u_i`` of its output already "done",
    delivered as that many extra prompt tokens and a correspondingly
    smaller output, so context lengths and finish times are spread from
    the first measured step and no cohort of slots finishes together."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.traffic, self.vocab = traffic, vocab
        self.rng = rng_for(seed, 1)
        self.n = int(traffic["generation"])
        self.pairs = length_multiset(traffic, self.n)
        strata = np.random.default_rng(
            int(traffic.get("pairing_seed", 0)) + 1).permutation(self.n)
        self.age_share = [(int(s) + 0.5) / self.n for s in strata]
        self._uid = 0
        self._generations = 0

    def next_generation(self) -> List[PlannedRequest]:
        first = self._generations == 0 and self.traffic.get("pre_age", True)
        self._generations += 1
        out = []
        for i in self.rng.permutation(self.n):
            p, o = self.pairs[int(i)]
            aged = min(int(self.age_share[int(i)] * o), o - 1) if first else 0
            out.append(PlannedRequest(
                uid=self._uid, new_tokens=o - aged, aged_tokens=aged,
                prompt=zipf_tokens(self.rng, self.vocab, p + aged)))
            self._uid += 1
        return out


# -------------------------------------------------------------- open loop
def stratified_order(rng: np.random.Generator, n: int,
                     block: int) -> List[int]:
    """A seeded order of the quantile indices 0..n-1 that keeps every
    stretch of ``block`` consecutive places representative of the whole
    distribution: place ``j`` of block ``b`` draws from the ``j``-th
    stratum (indices j*m .. (j+1)*m-1, m = number of blocks), each index
    used once; the seed shuffles which member of a stratum goes to which
    block and the order inside each block."""
    m = -(-n // block)                                  # blocks
    strata = [list(rng.permutation(np.arange(j * m, min((j + 1) * m, n))))
              for j in range(block)]
    order: List[int] = []
    for b in range(m):
        members = [int(st[b]) for st in strata if b < len(st)]
        order += [members[k] for k in rng.permutation(len(members))]
    return order


def arrival_gaps(n: int, length: float) -> List[float]:
    """The multiset of inter-arrival gaps of a Poisson process with
    ``n`` arrivals in ``length`` seconds: the exponential distribution
    at the quantiles (i + 0.5) / n, scaled to add up to ``length``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = length / sum(raw)
    return [g * scale for g in raw]


def openloop_schedule(traffic: dict, vocab: int, seed: int,
                      seconds: float) -> List[PlannedRequest]:
    """Requests sorted by due time.  A ramp of ``ramp_seconds`` runs
    the same arrival process before the window (due in [0, ramp)); the
    measured requests fall due in [ramp, ramp + seconds).  Each part has
    exactly round(rate * its length) requests, a fixed multiset of
    lengths and a fixed multiset of inter-arrival gaps (exponential, so
    arrivals bunch as a Poisson process's do); the seed only orders
    both, in blocks of ``order_block`` that each span the whole
    distribution, so that no seed front-loads the long prompts or the
    short gaps."""
    rng = rng_for(seed, 2)
    rate, ramp = float(traffic["rate_per_s"]), float(traffic["ramp_seconds"])
    block = int(traffic.get("order_block", 8))
    plan: List[PlannedRequest] = []
    uid = 0
    for start, length, measured in ((0.0, ramp, False),
                                    (ramp, float(seconds), True)):
        n = int(round(rate * length))
        if not n:
            continue
        pairs = length_multiset(traffic, n)
        gaps = arrival_gaps(n, length)
        # a request falls due in the middle of its own gap
        t = start
        dues = []
        for i in stratified_order(rng, n, block):
            dues.append(t + gaps[i] / 2)
            t += gaps[i]
        for j, i in enumerate(stratified_order(rng, n, block)):
            p, o = pairs[i]
            plan.append(PlannedRequest(
                uid=uid, prompt=zipf_tokens(rng, vocab, p), new_tokens=o,
                due_s=dues[j], measured=measured))
            uid += 1
    return plan


# ------------------------------------------------------------------ train
def train_batches(traffic: dict, vocab: int, seed: int,
                  global_batch: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``batch_pool`` batches of (tokens, targets), targets the tokens
    rolled by one as the trainer's own stream does it."""
    rng = rng_for(seed, 3)
    pool = []
    for _ in range(int(traffic["batch_pool"])):
        tokens = zipf_tokens(rng, vocab, (global_batch, int(traffic["seq"])))
        pool.append((tokens, np.roll(tokens, -1, axis=1)))
    return pool


# --------------------------------------------------------------- describe
def _summary(xs: Sequence[int]) -> Optional[dict]:
    if not xs:
        return None
    xs = sorted(xs)
    return {"n": len(xs), "min": xs[0], "median": xs[len(xs) // 2],
            "max": xs[-1], "mean": round(sum(xs) / len(xs), 2)}


def describe(requests: Sequence[PlannedRequest]) -> dict:
    """The distribution a plan realises, for the run's log."""
    return {
        "prompt": _summary([len(r.prompt) - r.aged_tokens for r in requests]),
        "output": _summary([r.new_tokens + r.aged_tokens for r in requests]),
        "aged": _summary([r.aged_tokens for r in requests]),
    }
