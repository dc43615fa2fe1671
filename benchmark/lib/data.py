"""The one general traffic generator: a seeded, learnable token source.

A traffic file (``benchmark/traffic/<name>.json``) gives ``seq_len``,
``global_batch`` and the ``source`` parameters; this module turns them and
``--seed`` into sequences. Sample ``i`` depends on ``(seed, i)`` only, so
the same seed gives the same batches whatever the batch size, the number of
chips or the prefetch depth, and the plain reference can ask for the first
global batch again.

``kind: zipf_bigram``: tokens are drawn from a Zipf law over the
configuration's vocabulary (token id = rank - 1), and at every odd position
the token is, with probability ``bigram_share``, a fixed function of the
token before it instead. A model that learns the unigram law and then the
rule lowers its loss from ln(V) well below the unigram entropy; uniform
random tokens would leave the loss flat, and a broken optimizer would pass.
"""

import numpy as np


class ZipfBigramSource:
    """Callable ``fetch(indices) -> (tokens, targets)`` for
    ``hvd.data.DistributedDataset`` (int32, shape ``(len(indices),
    seq_len)``; targets are the tokens shifted by one)."""

    def __init__(self, seed, vocab_size, seq_len, zipf_exponent=1.1,
                 bigram_share=0.5, bigram_multiplier=31, bigram_offset=7):
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self.bigram_share = float(bigram_share)
        self.mult = int(bigram_multiplier)
        self.offset = int(bigram_offset)
        ranks = np.arange(1, self.vocab_size + 1, dtype=np.float64)
        pmf = ranks ** -float(zipf_exponent)
        self._cdf = np.cumsum(pmf / pmf.sum())
        self._cdf[-1] = 1.0

    def sequence(self, index):
        rng = np.random.default_rng([self.seed, int(index)])
        n = self.seq_len + 1
        x = np.searchsorted(self._cdf, rng.random(n), side="right")
        x = np.minimum(x, self.vocab_size - 1).astype(np.int64)
        ruled = rng.random(n // 2) < self.bigram_share
        odd = np.arange(1, 2 * (n // 2), 2)
        follow = (x[odd - 1] * self.mult + self.offset) % self.vocab_size
        x[odd] = np.where(ruled, follow, x[odd])
        return x.astype(np.int32)

    def __call__(self, indices):
        rows = np.stack([self.sequence(i) for i in np.asarray(indices)])
        return rows[:, :-1], rows[:, 1:]


SOURCES = {"zipf_bigram": ZipfBigramSource}


def make_source(traffic, seed, vocab_size):
    """The generator a traffic file asks for, from its ``source`` block."""
    params = dict(traffic["source"])
    kind = params.pop("kind")
    if kind not in SOURCES:
        raise SystemExit(f"benchmark: traffic source kind {kind!r} is not "
                         f"one of {sorted(SOURCES)}")
    return SOURCES[kind](seed, vocab_size, traffic["seq_len"], **params)
