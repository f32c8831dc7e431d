"""The one general traffic generator: reads a mix's parameters, makes the
work from --seed. A mix is a data file under benchmark/traffic/.

kind "token_batches" (training): `batch` x `seq_len` token ids and as many
labels, uniform over the published vocabulary, a fresh pair every step.

kind "closed_loop" (serving): `clients` callers that each wait for their
reply and then send the next request at once. Prompt and reply lengths are
log-normal (`median`, `sigma`, clipped to `min`..`max`). The *set* of
lengths is drawn once from `size_seed` (`pool` pairs) and is the same for
every --seed; the seed orders it and draws the token ids, so that two seeds
do the same work in another order.
"""
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _rng(seed, stream):
    return np.random.default_rng([int(seed), int(stream)])


def token_batches(mix, vocab, seed):
    """Endless iterator of (ids, labels), int32 [batch, seq_len]."""
    rng = _rng(seed, 1)
    shape = (mix["batch"], mix["seq_len"])
    while True:
        yield (rng.integers(0, vocab, shape, dtype=np.int32),
               rng.integers(0, vocab, shape, dtype=np.int32))


def _lognormal(rng, spec, n):
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def request_sizes(mix):
    """The pool of (prompt length, new tokens): the same for every seed."""
    rng = _rng(mix["size_seed"], 2)
    return list(zip(_lognormal(rng, mix["prompt_len"], mix["pool"]).tolist(),
                    _lognormal(rng, mix["new_tokens"],
                               mix["pool"]).tolist()))


def requests(mix, vocab, seed):
    """Endless iterator of (prompt ids int32 [n], max_new_tokens): the pool
    in an order drawn from the seed, again and again in fresh orders."""
    sizes = request_sizes(mix)
    rng = _rng(seed, 3)
    while True:
        for i in rng.permutation(len(sizes)):
            n, new = sizes[i]
            yield rng.integers(0, vocab, (n,), dtype=np.int32), new
