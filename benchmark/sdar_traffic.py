"""Seed -> token batches for the `sdar-moe` family's training cells.

Token ids are Zipf-distributed (P(rank r) ~ r^-exponent) over the data rows
of the vocabulary slice, as text is, so routing is uneven; the rank -> id map
is a seeded permutation. The block-diffusion noise is drawn here, on the host,
into the batch: one level t a block, uniform on [t_min, 1] (BD3-LM's linear
schedule), each token of the block masked with probability t. Program and
reference therefore see the same masks. Every seed gives the same sizes.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def token_batches(seed: int, n_batches: int, batch: int, seq_len: int, block_length: int, data_rows: int,
                  zipf_exponent: float = 1.0, t_min: float = 1e-3) -> List[Dict[str, np.ndarray]]:
    """`n_batches` host batches: tokens (batch, seq_len) int32 below
    `data_rows`, masked (batch, seq_len) bool, noise_t (batch, seq_len /
    block_length) float32."""
    rng = np.random.default_rng(seed)
    weights = np.arange(1, data_rows + 1, dtype=np.float64) ** -zipf_exponent
    cdf = np.cumsum(weights / weights.sum())
    id_of_rank = rng.permutation(data_rows).astype(np.int32)
    out = []
    for _ in range(n_batches):
        ranks = np.minimum(np.searchsorted(cdf, rng.uniform(size=(batch, seq_len))), data_rows - 1)
        noise_t = rng.uniform(t_min, 1.0, (batch, seq_len // block_length)).astype(np.float32)
        masked = rng.uniform(size=(batch, seq_len)) < np.repeat(noise_t, block_length, axis=1)
        out.append({"tokens": id_of_rank[ranks], "masked": masked, "noise_t": noise_t})
    return out
