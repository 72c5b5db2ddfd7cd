"""One general traffic generator. A traffic mix is a data file of
parameters (``traffic/<name>.json``); this module turns it and a seed into
the requests of a run. Nothing here imports the program or JAX.

The work a run offers is a fixed multiset of (prompt, output) lengths: the
seed only permutes which arrival gets which pair, jitters the arrival
inside its slot, and draws the token ids."""

from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclass(frozen=True)
class Request:
    index: int  # < 0: lead-in (set-up), >= 0: measured
    due_s: float  # seconds after the first measured instant (open loop)
    prompt_len: int
    output_len: int
    token_seed: int  # the prompt's token ids are drawn from this


def _quantile_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths at evenly spaced quantiles ((i + 0.5) / n) of the
    distribution ``spec`` describes, clipped to [lo, hi]."""
    lo, hi = int(spec["clip"][0]), int(spec["clip"][1])
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        raw = [math.exp(mu + sigma * _NORMAL.inv_cdf(q)) for q in qs]
    elif spec["dist"] == "uniform":
        raw = [lo + (hi - lo) * q for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(hi, max(lo, round(x)))) for x in raw]


def length_multiset(lengths: Dict[str, Any], n: int) -> List[Tuple[int, int]]:
    """The fixed multiset of ``n`` (prompt, output) pairs of a mix: both
    marginals at evenly spaced quantiles, paired by a permutation that
    depends only on the file's ``pairing_seed`` and ``n``, never on the
    run's seed."""
    prompts = _quantile_lengths(lengths["prompt"], n)
    outputs = _quantile_lengths(lengths["output"], n)
    perm = np.random.default_rng([int(lengths.get("pairing_seed", 0)), n]).permutation(n)
    return [(prompts[i], outputs[int(perm[i])]) for i in range(n)]


def _seeded(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def paced_schedule(traffic: Dict[str, Any], seed: int, seconds: float) -> List[Request]:
    """Open loop at the fixed rate of the file: one arrival in every slot
    of length 1/rate, at a uniformly random instant inside its slot. The
    lead-in slots come before instant 0 and carry a multiset of their own.
    A pure function of (traffic, seed, seconds)."""
    rate = float(traffic["rate_per_s"])
    slot = 1.0 / rate
    n = int(math.floor(seconds * rate))
    lead = int(traffic["lead_in_requests"])
    out: List[Request] = []
    for first, count, stream in ((-lead, lead, 1), (0, n, 2)):
        if count == 0:
            continue
        pairs = length_multiset(traffic["lengths"], count)
        rng = _seeded(seed, stream)
        order = rng.permutation(count)
        jitter = rng.random(count)
        token_seeds = rng.integers(0, 2**31 - 1, size=count)
        for i in range(count):
            p, o = pairs[int(order[i])]
            out.append(
                Request(first + i, (first + i + float(jitter[i])) * slot, p, o, int(token_seeds[i]))
            )
    return out


def closed_stream(traffic: Dict[str, Any], seed: int) -> List[Request]:
    """Closed loop: the shared list clients take their next request from.
    It is made of rounds; every round is the mix's whole fixed multiset in
    an order the seed permutes, so any stretch of a run offers the same
    work whatever the seed, and a run differs from another only inside its
    last, unfinished round. The driver cycles the list if a run outlasts it."""
    count, rounds = int(traffic["multiset_size"]), int(traffic.get("rounds", 1))
    pairs = length_multiset(traffic["lengths"], count)
    rng = _seeded(seed, 3)
    out: List[Request] = []
    for r in range(rounds):
        order = rng.permutation(count)
        token_seeds = rng.integers(0, 2**31 - 1, size=count)
        out += [
            Request(r * count + i, 0.0, *pairs[int(order[i])], int(token_seeds[i]))
            for i in range(count)
        ]
    return out


def prompt_tokens(req: Request, vocab_size: int) -> List[int]:
    """Distinct random token ids per request (no shared prefix)."""
    rng = np.random.default_rng(req.token_seed)
    return rng.integers(1, vocab_size, size=req.prompt_len).tolist()


def describe(requests: Sequence[Request]) -> Dict[str, Any]:
    measured = [r for r in requests if r.index >= 0]
    return {
        "requests": len(measured),
        "lead_in": len(requests) - len(measured),
        "prompt_tokens": sum(r.prompt_len for r in measured),
        "output_tokens": sum(r.output_len for r in measured),
        "prompt_len_max": max((r.prompt_len for r in measured), default=0),
    }
