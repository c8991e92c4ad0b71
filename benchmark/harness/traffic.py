"""One general traffic generator. A traffic mix is a data file of parameters
that this file reads; a new mix needs no code.

Every seed gets the same requests in size and in order: lengths are the
mid-quantiles of the mix's distribution, paired and ordered once by fixed
shuffles, so that runs with different seeds do the same work; the seed draws
the token ids (and the weights). An order drawn from the seed was tried first
(PR 24): a request lasts a third of the window, so the order decides how many
requests complete, and so how many prompts are admitted and chunked, inside
it: the completed tokens a second spread by 3.6% between seeds and by nothing
between two runs of one seed (PERF.md, section 6, has the readings).

Kinds:
  train         batches of seeded token ids, ``batch`` x ``seq``
  serve_closed  ``clients`` callers, each issuing its next request when the
                last completes
  serve_open    arrivals at a fixed ``rate_per_s`` (``poisson``, or ``bursty``
                in groups of ``burst``), each request timed from the instant
                it was due
"""
import math

import numpy as np

PAIRING_SEED = 20260930      # the fixed shuffle that pairs prompt and output


def _quantiles(spec, n):
    """n lengths at the mid-quantiles of the distribution ``spec``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    dist = spec.get("dist", "uniform")
    if lo == hi:
        x = np.full(n, lo, float)
    elif dist == "uniform":
        x = lo + u * (hi - lo)
    elif dist == "log_uniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist == "pareto":             # heavy tail, clipped at ``max``
        x = np.minimum(lo * (1 - u) ** (-1.0 / spec.get("alpha", 1.5)), hi)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.round(x), lo, hi).astype(int)


def request_sizes(mix, lap):
    """[(prompt_len, output_len)] x ``pool``: the same pairs in the same order
    for every seed; ``lap`` 0 is the ramp's order, lap n the order of the
    n-th pass through the pool."""
    n = mix.get("pool", 256)
    prompts = _quantiles(mix["prompt_len"], n)
    outputs = _quantiles(mix["output_len"], n)
    outputs = outputs[np.random.default_rng(PAIRING_SEED).permutation(n)]
    order = np.random.default_rng([PAIRING_SEED, 1, lap]).permutation(n)
    return [(int(prompts[i]), int(outputs[i])) for i in order]


class RequestSource:
    """The stream of requests of a serving mix: sizes from the pool in its
    fixed order (the pool repeats, reshuffled, if a run outlasts it), token
    ids from the seed."""

    def __init__(self, mix, seed, vocab):
        self.mix, self.seed, self.vocab = mix, int(seed), vocab
        self.rng = np.random.default_rng([self.seed, 2])
        self.sizes = request_sizes(mix, 1)
        self.n = 0

    def next(self):
        """(prompt ids int32, max_new_tokens)"""
        i = self.n
        self.n += 1
        if i and i % len(self.sizes) == 0:
            self.sizes = request_sizes(self.mix, 1 + i // len(self.sizes))
        plen, olen = self.sizes[i % len(self.sizes)]
        return self.rng.integers(0, self.vocab, plen).astype(np.int32), olen

    def ramp(self, n):
        """The ``n`` requests that fill the system before the window opens:
        the same sizes for every seed (lap 0 of the pool), each output cut
        at a point of its own, (i + 1)/n of
        its length, so that completions are staggered from the first second
        and the window opens on the same state whatever the seed. Token ids
        come from the seed. These requests are in no tail."""
        fixed = request_sizes(self.mix, 0)
        out = []
        for i in range(n):
            plen, olen = fixed[i % len(fixed)]
            olen = max(1, (olen * (i + 1)) // n)
            prompt = self.rng.integers(0, self.vocab, plen).astype(np.int32)
            out.append((prompt, olen))
        return out


def arrival_times(mix, horizon_s):
    """Due instants (seconds from the ramp's start) of an open loop at the
    mix's fixed rate, up to ``horizon_s``: exponential gaps at their
    mid-quantiles in one fixed shuffle, the same for every seed; ``bursty``
    groups ``burst`` arrivals on one instant."""
    rate = mix["rate_per_s"]
    burst = mix.get("burst", 1) if mix.get("arrivals") == "bursty" else 1
    n_groups = max(1, int(math.ceil(rate * horizon_s / burst)))
    u = (np.arange(n_groups) + 0.5) / n_groups
    gaps = -np.log1p(-u) * burst / rate
    gaps = gaps[np.random.default_rng([PAIRING_SEED, 3]).permutation(n_groups)]
    starts = np.cumsum(gaps) - gaps[0]
    due = np.repeat(starts, burst)
    return due[due < horizon_s]


def train_batch(mix, seed, step, vocab):
    """Batch ``step`` of a training mix: rows of seeded token ids that all
    differ, made on the host."""
    rng = np.random.default_rng([int(seed), 4, int(step)])
    return rng.integers(0, vocab, (mix["batch"], mix["seq"])).astype(np.int32)
