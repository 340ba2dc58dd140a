"""The one traffic generator: every mix is a data file of parameters in
bench/traffic/, read here.

Every seed gets the same multiset of sizes and arrival gaps, in another
order, so that seeds differ in order and token ids but not in the
amount of work. Lengths are the quantiles of a clipped lognormal at
(i + 0.5) / n; gaps are the quantiles of an exponential. The order is
balanced: every run of `STRATA` consecutive requests holds one length
from each of `STRATA` quantile bands, so any prefix of the queue (what
a window gets through) carries the same mix. A mix that names a
`schedule_seed` replays one schedule: its order of lengths and gaps is
drawn from that seed, and only the token ids from the run's seed.

Open-loop arrivals may start `warmup_s` before the window opens, so
that the window finds the system in its steady state; those requests
are a mix of their own, due before the window, and are not measured.

Arrival machinery follows the repo's serving/workload.py (Poisson gaps
per request); the lengths are drawn here.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, NamedTuple

import numpy as np

STRATA = 16


class Request(NamedTuple):
    due_s: float          # seconds after the window opens (< 0: warm-up)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for `seed` (any whole number) and a stream."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2 ** 64, *stream]))


def jax_seed(seed: int) -> int:
    """A 32-bit seed for jax.random, derived from any whole number."""
    return int(np.random.SeedSequence([seed % 2 ** 64, 7]).generate_state(1)[0])


def lognormal_grid(n: int, spec: Dict) -> np.ndarray:
    """n lengths: clipped lognormal quantiles, rounded to whole tokens."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def balanced_order(n: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of range(n) (n sorted quantiles) in which each run
    of STRATA consecutive entries takes one from each quantile band."""
    bands = [rng.permutation(b) for b in np.array_split(np.arange(n), STRATA)]
    out: List[int] = []
    for r in range(max(len(b) for b in bands)):
        rnd = [b[r] for b in bands if r < len(b)]
        out.extend(rng.permutation(rnd).tolist())
    return np.asarray(out)


def n_requests(traffic: Dict, seconds: float) -> int:
    arr = traffic["arrivals"]
    if arr["process"] == "poisson":
        return max(1, int(round(arr["rate_per_s"] * seconds)))
    if arr["process"] == "all_at_start":
        return int(arr["requests"])
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def due_times(traffic: Dict, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    """Seconds after the stretch opens at which each request is due.
    Poisson: n exponential-quantile gaps in a seeded order, scaled so
    the n arrivals fall inside the stretch; all_at_start: all at 0."""
    arr = traffic["arrivals"]
    if arr["process"] == "all_at_start":
        return np.zeros(n)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    t = np.cumsum(gaps)
    return t * (seconds * n / (n + 0.5)) / t[-1]


def _stretch(traffic: Dict, seconds: float, order: np.random.Generator,
             ids: np.random.Generator, vocab: int,
             start: float) -> List[Request]:
    n = n_requests(traffic, seconds)
    prompts = lognormal_grid(n, traffic["prompt_tokens"])
    outputs = lognormal_grid(n, traffic["output_tokens"])
    p_len = prompts[balanced_order(n, order)]
    o_len = outputs[balanced_order(n, order)]
    due = start + due_times(traffic, n, seconds, order)
    return [Request(float(due[i]),
                    ids.integers(0, vocab, int(p_len[i]), dtype=np.int32),
                    int(o_len[i])) for i in range(n)]


def serve_requests(traffic: Dict, vocab: int, seed: int,
                   seconds: float) -> List[Request]:
    """The requests of one serving run, in due order: those of the
    warm-up stretch (due before 0), then those of the window."""
    warm = traffic["arrivals"].get("warmup_s", 0)
    order = rng_for(traffic.get("schedule_seed", seed), 1)
    ids = rng_for(seed, 4)
    reqs = _stretch(traffic, warm, order, ids, vocab, -warm) if warm else []
    return reqs + _stretch(traffic, seconds, order, ids, vocab, 0.0)


def prefill_shapes(traffic: Dict, lengths) -> List[int]:
    """The prefill lengths the engine compiles for these prompts: its
    bucket rule, a multiple of the chunk, or the whole prompt where it
    is shorter than one."""
    chunk = traffic["engine"]["prefill_chunk"]
    return sorted({(n - n % chunk) or n for n in lengths})


def train_batch(seed: int, step: int, batch: int, seq: int,
                vocab: int) -> Dict[str, np.ndarray]:
    """Rows of random tokens for training step `step` (1-based): every
    step and every row differs."""
    t = rng_for(seed, 2, step).integers(0, vocab, (batch, seq + 1),
                                        dtype=np.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile; nan for no values."""
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))
