"""Host batches for the token families: token ids from a seeded Zipf source
and, for `sdar-moe`, the block-diffusion noise drawn beside them (BD3-LM's
linear schedule: each block of `block_length` tokens gets one noise level t,
uniform on [t_min, 1], and each of its tokens is masked with probability t;
the loss weighs a masked token by 1 / t).

A batch is `tokens` (B, L) int32, `masked` (B, L) bool, `noise_t`
(B, L / block_length) float32: what `train/families.SDARMoEFamily` declares;
with `block_length` 0 it is `tokens` alone, one document a row, what
`GraniteHybridFamily` declares. A loader of real documents (packing, attention
and the state-space scan cut at document boundaries) is not here yet
(ROADMAP.md).
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


class TokenBatches:
    """An endless iterable of batches: ids Zipf-distributed (exponent 1) over
    `vocab_rows`, ids and noise drawn from `seed`; no noise with
    `block_length` 0."""

    def __init__(self, batch_size: int, seq_len: int, block_length: int, vocab_rows: int, seed: int = 0,
                 t_min: float = 1e-3):
        if block_length and seq_len % block_length:
            raise ValueError(f"a sequence of {seq_len} does not hold whole blocks of {block_length}")
        self.batch_size, self.seq_len, self.block_length = batch_size, seq_len, block_length
        self.vocab_rows, self.seed, self.t_min = vocab_rows, seed, t_min

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        rng = np.random.default_rng(self.seed)
        weights = 1.0 / np.arange(1, self.vocab_rows + 1, dtype=np.float64)
        cdf = np.cumsum(weights / weights.sum())
        shape = (self.batch_size, self.seq_len)
        while True:
            tokens = np.minimum(np.searchsorted(cdf, rng.uniform(size=shape)), self.vocab_rows - 1)
            if not self.block_length:
                yield {"tokens": tokens.astype(np.int32)}
                continue
            noise_t = rng.uniform(self.t_min, 1.0, (self.batch_size, self.seq_len // self.block_length))
            noise_t = noise_t.astype(np.float32)
            masked = rng.uniform(size=shape) < np.repeat(noise_t, self.block_length, axis=1)
            yield {"tokens": tokens.astype(np.int32), "masked": masked, "noise_t": noise_t}
