"""Traffic generation from a traffic file (stdlib only: the load generator
imports it too, and it never imports JAX).

A traffic file (``bench/traffic/<name>.json``) holds parameters, never code:

  loop            "open" (arrivals on a schedule) or "closed" (each client
                  sends its next request when the previous one finishes)
  rate_per_s      open loop: mean arrival rate
  burstiness      open loop: share of gaps drawn at 4x the rate (0 = Poisson)
  clients         closed loop: a number, or "max_batch" for the
                  configuration's decode batch
  input, output   lognormal lengths: {"mean", "sigma", "cap"}
  max_total       prompt + output are clipped to this many tokens
  prompt_multiple prompts are rounded up to a multiple of this
  pool            closed loop: size of the stratified length pool (below)
  preroll_s       seconds of traffic before the measured window opens
  drain_s         seconds after the window closes before open streams are cut

Every seed gets the same work in another order.  Lengths come from a pool of
(input, output) pairs taken at evenly spaced quantiles of the two
lognormals, paired by a fixed permutation.  An open loop's pool is all of
its arrivals, sorted by output length and laid over the run in one fixed
golden-ratio order, so every stretch of the run holds short and long
outputs alike; the seed shuffles it within blocks of four consecutive
arrivals, so the load a request meets does not hang on the seed.  Its gaps
are evenly spaced quantiles of the exponential (or the burst mixture) in
one fixed shuffled order, scaled to fill ``preroll_s + seconds`` exactly:
every seed has the same arrival times.  A closed loop, which cannot know
how many requests it will send, takes ``pool`` pairs, shuffled by the seed
block after block.  The seed also draws the prompt token ids, uniform over
the vocabulary.

The lognormal and the burst mixture follow ``sim/traces.py`` of the program
(``_lognormal_clipped``, ``azure_conversation_lengths``, ``_poisson_gap``).
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Dict, List, Sequence, Tuple

_POOL_PAIRING_SEED = 0   # fixed: the pool is the same for every run seed
_ARRIVAL_ORDER_SEED = 0  # fixed: the arrival times are the same for every seed
_GOLDEN = (math.sqrt(5) - 1) / 2
_SHUFFLE_BLOCK = 4       # open loop: the seed reorders arrivals this far apart


def lognormal_quantiles(n: int, mean: float, sigma: float,
                        cap: int) -> List[int]:
    """``n`` lengths at the quantiles (i + 1/2) / n of a lognormal whose
    unclipped mean is ``mean``, truncated to an int and clipped to
    [1, cap] as ``sim/traces._lognormal_clipped`` does."""
    mu = math.log(mean) - sigma ** 2 / 2
    z = NormalDist()
    return [max(1, min(cap, int(math.exp(mu + sigma * z.inv_cdf(
        (i + 0.5) / n))))) for i in range(n)]


def length_pool(spec: Dict) -> List[Tuple[int, int]]:
    """The (prompt, output) lengths every seed draws from."""
    n = int(spec["pool"])
    inp = lognormal_quantiles(n, **spec["input"])
    out = lognormal_quantiles(n, **spec["output"])
    perm = list(range(n))
    random.Random(_POOL_PAIRING_SEED).shuffle(perm)
    mult = int(spec.get("prompt_multiple", 1))
    total = int(spec["max_total"])
    pool = []
    for i in range(n):
        p = -(-inp[i] // mult) * mult
        p = min(p, total - mult)
        o = max(1, min(out[perm[i]], total - p))
        pool.append((p, o))
    return pool


def request_lengths(spec: Dict, seed: int, n: int) -> List[Tuple[int, int]]:
    """A closed loop's first ``n`` (prompt, output) lengths for ``seed``: the
    pool shuffled block after block, so every prefix of k whole blocks
    holds exactly k copies of the pool."""
    pool = length_pool(spec)
    rng = random.Random(seed)
    seq: List[Tuple[int, int]] = []
    while len(seq) < n:
        block = list(pool)
        rng.shuffle(block)
        seq.extend(block)
    return seq[:n]


def arrival_count(spec: Dict, horizon_s: float) -> int:
    """Open loop: requests whose arrivals fill ``horizon_s``."""
    b = float(spec.get("burstiness", 0.0))
    mean_gap = ((1 - b) + b / 4.0) / float(spec["rate_per_s"])
    return max(1, round(horizon_s / mean_gap))


def arrival_times(spec: Dict, horizon_s: float) -> List[float]:
    """Open loop: due times in (0, horizon_s], seconds after the start, the
    same for every seed.

    The gaps are quantiles of the exponential at ``rate_per_s`` (a share
    ``burstiness`` of them at four times the rate, as in
    ``sim/traces._poisson_gap``), in one fixed shuffled order, then scaled
    so the last request is due exactly at ``horizon_s``."""
    n = arrival_count(spec, horizon_s)
    rate = float(spec["rate_per_s"])
    n_burst = round(float(spec.get("burstiness", 0.0)) * n)
    gaps = []
    for m, r in ((n - n_burst, rate), (n_burst, 4.0 * rate)):
        gaps += [-math.log(1.0 - (i + 0.5) / m) / r for i in range(m)]
    random.Random(_ARRIVAL_ORDER_SEED).shuffle(gaps)
    scale = horizon_s / sum(gaps)
    t, out = 0.0, []
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def spread_order(n: int) -> List[int]:
    """Ranks 0..n-1 laid evenly over n positions: position i takes the rank
    of ``frac(i * golden)`` among all n such numbers, so any k consecutive
    positions hold ranks from every part of the range."""
    keys = [(i * _GOLDEN) % 1.0 for i in range(n)]
    rank = [0] * n
    for r, i in enumerate(sorted(range(n), key=keys.__getitem__)):
        rank[i] = r
    return rank


def open_loop_lengths(spec: Dict, seed: int, n: int) -> List[Tuple[int, int]]:
    """An open loop's ``n`` (prompt, output) lengths: the pool of ``n``
    pairs sorted by output length, in the spread order, shuffled by the
    seed within blocks of ``_SHUFFLE_BLOCK`` arrivals."""
    pool = sorted(length_pool(dict(spec, pool=n)), key=lambda p: (p[1], p[0]))
    order = spread_order(n)
    rng = random.Random(seed)
    for b in range(0, n, _SHUFFLE_BLOCK):
        block = order[b:b + _SHUFFLE_BLOCK]
        rng.shuffle(block)
        order[b:b + _SHUFFLE_BLOCK] = block
    return [pool[r] for r in order]


def prompt_tokens(seed: int, idx: int, length: int, vocab: int) -> List[int]:
    """Prompt ``idx`` of run ``seed``: ids uniform over the vocabulary.  A
    string seed hashes the same in every process."""
    rng = random.Random(f"{seed}:{idx}")
    return [int(rng.random() * vocab) for _ in range(length)]


def clients(spec: Dict, max_batch: int) -> int:
    c = spec.get("clients", "max_batch")
    return max_batch if c == "max_batch" else int(c)


def schedule(spec: Dict, seed: int, seconds: float, max_batch: int,
             closed_pool: int = 4096) -> Dict:
    """The run's requests, relative to the start of the pre-roll.

    Open loop: one entry per arrival with its ``due`` time.  Closed loop:
    a sequence of ``closed_pool`` entries that the clients take in order
    (far more than a window can serve)."""
    horizon = float(spec["preroll_s"]) + float(seconds)
    if spec["loop"] == "open":
        due = arrival_times(spec, horizon)
        lens = open_loop_lengths(spec, seed, len(due))
        reqs = [{"idx": i, "due": d, "prompt_len": p, "max_tokens": o}
                for i, (d, (p, o)) in enumerate(zip(due, lens))]
    elif spec["loop"] == "closed":
        lens = request_lengths(spec, seed, closed_pool)
        reqs = [{"idx": i, "prompt_len": p, "max_tokens": o}
                for i, (p, o) in enumerate(lens)]
    else:
        raise ValueError(f"loop must be 'open' or 'closed', not "
                         f"{spec['loop']!r}")
    return {"loop": spec["loop"], "clients": clients(spec, max_batch),
            "requests": reqs}


def prompt_lengths(sched: Dict) -> Sequence[int]:
    """Distinct prompt lengths a schedule can send (what set-up warms)."""
    return sorted({r["prompt_len"] for r in sched["requests"]})
