"""The one traffic generator: turns a mix file and a seed into requests.

Sizes and their order do not depend on the seed.  A mix names a
lognormal (median, sigma, clip) for prompt and for output lengths;
``sizes`` takes ``distinct`` quantiles of each, at ``(j + 0.5) /
distinct``, and pairs them in a fixed order.  The order in which they
arrive is drawn once, from a fixed seed: a timed window holds only part
of the stream, and an order drawn from the run's seed changed how many
requests the window admitted and with them its rate.  The run's seed
draws the token ids (and, elsewhere, the weights), so every seed brings
the same work with other numbers.

Serving is a backlog: every request is waiting when the run starts.
The order keeps the mix even along the stream: sizes are cut into
``strata`` groups by prompt length, and each run of ``strata``
consecutive requests holds one size of every group.

The run should not open on a batch of requests that all started
together (their outputs would end in lockstep).  So the first ``slots``
requests stand for requests caught mid-flight in a steady state:
request ``j`` is drawn with probability proportional to its output
length (the time it holds a slot) at quantile ``(j + 0.5) / slots``, has
already produced a fraction ``(perm[j] + 0.5) / slots`` of its output,
and enters with those tokens appended to its prompt and only the rest
left to produce.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

#: pairs prompt and output quantiles, and orders the stream; fixed, so
#: neither sizes nor order follow the run's seed
_PAIRING_SEED = 20190830
_ORDER_SEED = _PAIRING_SEED + 2


def lognormal_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at quantiles ``(j + 0.5) / n`` of a lognormal with
    ``spec``'s median and sigma, clipped to ``[min, max]``, ascending."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((j + 0.5) / n) for j in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def sizes(mix: dict) -> list:
    """The mix's ``(prompt_len, output_len)`` pairs, sorted by prompt."""
    n = int(mix["distinct"])
    p = lognormal_sizes(mix["prompt"], n)
    o = lognormal_sizes(mix["output"], n)
    o = o[np.random.default_rng(_PAIRING_SEED).permutation(n)]
    return [(int(a), int(b)) for a, b in zip(p, o)]


@dataclasses.dataclass
class ServeRequest:
    prompt: np.ndarray        # int32 token ids
    max_new: int
    initial: bool             # one of the mid-flight requests the run opens on


def _epoch_order(pairs: list, strata: int, rng) -> list:
    n = len(pairs)
    if n % strata:
        raise ValueError(f"distinct ({n}) must be a multiple of strata "
                         f"({strata})")
    groups = [list(rng.permutation(pairs[g * (n // strata):
                                         (g + 1) * (n // strata)]))
              for g in range(strata)]
    out = []
    for r in range(n // strata):
        round_ = [groups[g][r] for g in range(strata)]
        out.extend(round_[i] for i in rng.permutation(strata))
    return [(int(a), int(b)) for a, b in out]


def initial_sizes(mix: dict) -> list:
    """``(prompt_len, output_len)`` of the ``slots`` mid-flight requests:
    length-biased picks, each with part of its output already in its
    prompt.  Seed-independent."""
    pairs = sorted(sizes(mix), key=lambda s: s[1])
    slots = int(mix["slots"])
    w = np.cumsum([o for _, o in pairs], dtype=float)
    w /= w[-1]
    ages = np.random.default_rng(_PAIRING_SEED + 1).permutation(slots)
    out = []
    for j in range(slots):
        p, o = pairs[int(np.searchsorted(w, (j + 0.5) / slots))]
        done = int((ages[j] + 0.5) / slots * o)
        done = min(done, o - 1)
        out.append((p + done, o - done))
    return out


def serve_requests(mix: dict, seed: int, vocab: int) -> list:
    """The run's request stream: ``slots`` mid-flight requests, then
    ``epochs`` passes over the mix's sizes, in the fixed order; token ids
    from ``seed``."""
    order = np.random.default_rng(_ORDER_SEED)
    init = initial_sizes(mix)
    init = [init[i] for i in order.permutation(len(init))]
    stream = []
    for _ in range(int(mix["epochs"])):
        stream.extend(_epoch_order(sizes(mix), int(mix["strata"]), order))
    rng = np.random.default_rng(seed)
    out = []
    for k, (p, o) in enumerate(init + stream):
        toks = rng.integers(0, vocab, size=p, dtype=np.int64)
        out.append(ServeRequest(prompt=toks.astype(np.int32), max_new=o,
                                initial=k < len(init)))
    return out


def train_batch(job: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """Step ``step``'s rows: ``[batch, seq + 1]`` token ids, every row of
    every step its own draw."""
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab, size=(int(job["batch"]),
                                        int(job["seq"]) + 1),
                        dtype=np.int64).astype(np.int32)
