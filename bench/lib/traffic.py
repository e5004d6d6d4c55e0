"""The one traffic generator: turns a mix's data file into requests.

Every seed gets the same multiset of sizes and inter-arrival gaps, in
its own order: sizes are the distribution's quantiles at
``(i + 0.5) / n``, gaps the exponential's, and the seed orders them.
The order is stratified: the sorted values fall into ``STRATA`` equal
bands, and every run of ``STRATA`` consecutive requests holds one value
of each band, in an order drawn from the seed.  So seeds differ in which
request comes when, not in how much work a stretch of the window holds,
and two seeds' runs spread about as little as two runs of one seed.
This is smoother than Poisson arrivals with independent sizes: a
stretch of the window holds neither the clusters of arrivals nor the
runs of long prompts that independent draws would give.  Token ids and
class labels are drawn from the seed.

A mix file holds:

* ``loop``: ``open`` (arrivals at ``rate_per_s``, exponential gaps in
  the stratified order) or ``closed`` (``clients`` that each resubmit
  when their request finishes);
* ``stagger`` (closed loop): the clients' first requests start part-way
  through their answers, at fractions spread evenly over the answer in
  an order drawn from the seed, as in a loop that has run for long; the
  tokens already answered are prefilled with the prompt;
* ``prompt_tokens`` / ``output_tokens``: ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
  "max"}``; ``output_tokens`` may be ``{"fill_context": true}``;
* image mixes (a queue that always holds work): ``num_steps``,
  ``cfg_scale``, ``method``, ``classes``.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator per purpose, so adding one draw never shifts another."""
    tag = int.from_bytes(stream.encode(), "little") % (2 ** 63)
    return np.random.default_rng([int(seed) % (2 ** 64), tag])


def key_seed(seed: int) -> int:
    """The seed folded into the 31 bits a JAX PRNG key takes."""
    return int(rng_for(seed, "weights").integers(0, 2 ** 31 - 1))


def quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(x, dist["min"]), dist["max"])


STRATA = 8


def stratified_order(values: np.ndarray, rng: np.random.Generator
                     ) -> np.ndarray:
    """``values`` (sorted, ``len`` a multiple of ``STRATA``) reordered so
    that each block of ``STRATA`` holds one value of every band."""
    bands = values.reshape(STRATA, -1)                 # band j: row j
    bands = np.stack([rng.permutation(b) for b in bands])
    blocks = bands.T                                   # block b: column b
    return np.concatenate([rng.permutation(b) for b in blocks])


def _quantiles(fn, n: int) -> np.ndarray:
    """``fn`` at the ``n`` mid-quantiles, sorted, padded up to a whole
    number of blocks (the extra requests come after the window)."""
    m = -(-n // STRATA) * STRATA
    return np.array([fn((i + 0.5) / m) for i in range(m)])


def sizes(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles, stratified order."""
    vals = _quantiles(lambda u: round(quantile(dist, u)), n).astype(np.int64)
    return stratified_order(vals, rng)[:n]


def arrival_times(rate: float, n: int, rng: np.random.Generator
                  ) -> np.ndarray:
    """Due times (s) of ``n`` arrivals at ``rate``: exponential gap
    quantiles in stratified order, summed."""
    gaps = _quantiles(lambda u: -math.log(1.0 - u) / rate, n)
    return np.cumsum(stratified_order(gaps, rng))[:n]


def lm_requests(mix: dict, seed: int, n: int, max_len: int) -> list[dict]:
    """``n`` LM requests: uid, due time (open loop; 0 otherwise), prompt
    length, output budget and the tokens of it already answered (prefilled
    with the prompt; ``stagger`` only).  A pure function of (mix, seed,
    n); ``n`` rounds up to whole blocks, so every seed gets the same
    sizes."""
    n = -(-n // STRATA) * STRATA
    rng = rng_for(seed, "lm-sizes")
    plen = sizes(mix["prompt_tokens"], n, rng)
    out = mix["output_tokens"]
    if out.get("fill_context"):
        # the prompt plus its answer fill the table; one position stays
        # free for the engine's last write
        olen = max_len - 1 - plen
    else:
        olen = sizes(out, n, rng)
    if mix["loop"] == "open":
        due = arrival_times(mix["rate_per_s"], n, rng_for(seed, "arrivals"))
    else:
        due = np.zeros(n)
    answered = np.zeros(n, np.int64)
    if mix.get("stagger"):
        k = mix["clients"]
        frac = (rng_for(seed, "phases").permutation(k) + 0.5) / k
        answered[:k] = np.round(frac * olen[:k])
    return [{"uid": i, "due_s": float(due[i]), "prompt_len": int(plen[i]),
             "max_new": int(olen[i]), "answered": int(answered[i])}
            for i in range(n)]


def prompt_tokens(seed: int, uid: int, length: int, vocab: int
                  ) -> np.ndarray:
    """A request's prompt: any token ids, from the seed and its uid."""
    return rng_for(seed, f"prompt-{uid}").integers(
        0, vocab, length).astype(np.int32)


def image_labels(mix: dict, seed: int, n: int) -> list[int]:
    return [int(x) for x in
            rng_for(seed, "labels").integers(0, mix["classes"], n)]
