"""Operations and bytes the `granite-hybrid` family's algorithm requires,
from shapes alone; each `fn(config, spec)` as benchmark/families.py calls it.

Forward, per position and layer: the mixer's projections (a Mamba-2 layer's
in- and out-projection and its convolution's taps; an attention layer's q, k,
v, o) and the gated MLP's two products. The state-space scan is counted in
its chunked form at `mamba_chunk_size` Q, for the VISIBLE pairs only (key j at
or before query i inside a chunk: Q (Q + 1) / 2 a chunk): the scores C B^T
once a chunk (the heads share B and C), a head's output over them, a chunk's
own state, the carried state's part of the output, the carry itself.
Attention likewise: L (L + 1) / 2 visible pairs a head. The head and loss run
at the L - 1 positions that predict. Training is three forwards and no
recomputation; nothing here knows the program's tiles, head groups or remat.
"""

from __future__ import annotations

from typing import Dict

_BF16, _F32 = 2, 4


def _sizes(config: Dict, spec: Dict) -> Dict[str, int]:
    seq, q = spec["seq_len"], config["mamba_chunk_size"]
    if seq % q:
        raise ValueError(f"granite_counts: {seq} positions are not whole chunks of {q}")
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    return {
        "d": config["hidden_size"], "f": config["shared_intermediate_size"], "v": config["vocab_size"],
        "hq": config["num_attention_heads"], "hkv": config["num_key_value_heads"],
        "hd": config["hidden_size"] // config["num_attention_heads"],
        "h": heads, "p": p, "n": config["mamba_d_state"], "inner": heads * p,
        "channels": heads * p + 2 * config["mamba_d_state"], "k": config["mamba_d_conv"],
        "q": q, "chunks": seq // q, "chunk_pairs": q * (q + 1) // 2,
        "seq": seq, "batch": spec["batch"], "pairs": seq * (seq + 1) // 2,
        "mamba_layers": config["layer_types"].count("mamba"),
        "attention_layers": config["layer_types"].count("attention"),
    }


def _chunk_local_flops(s: Dict[str, int]) -> float:
    """One sample, one layer: every head's output over a chunk's visible
    pairs (the scores given) and every chunk's own state."""
    return s["chunks"] * s["h"] * (2 * s["p"] * s["chunk_pairs"] + 2 * s["q"] * s["n"] * s["p"])


def _scan_flops(s: Dict[str, int]) -> float:
    """One sample, one layer: the chunk-local part, the scores C B^T, the
    carried state's part of the output and the carry."""
    scores = s["chunks"] * 2 * s["n"] * s["chunk_pairs"]
    past = s["chunks"] * s["h"] * 2 * s["q"] * s["n"] * s["p"]
    carry = s["chunks"] * s["h"] * 2 * s["n"] * s["p"]
    return _chunk_local_flops(s) + scores + past + carry


def forward_flops_per_sample(config: Dict, spec: Dict) -> float:
    s = _sizes(config, spec)
    mlp = 2 * s["d"] * 2 * s["f"] + 2 * s["f"] * s["d"]
    mamba = 2 * s["d"] * (s["inner"] + s["channels"] + s["h"]) + 2 * s["k"] * s["channels"] + 2 * s["inner"] * s["d"]
    attention = 2 * s["d"] * s["hd"] * (2 * s["hq"] + 2 * s["hkv"])
    layers = s["mamba_layers"] * (s["seq"] * (mamba + mlp) + _scan_flops(s))
    layers += s["attention_layers"] * (s["seq"] * (attention + mlp) + 4 * s["hd"] * s["hq"] * s["pairs"])
    return layers + (s["seq"] - 1) * 2 * s["d"] * s["v"]


def train_flops_per_sample(config: Dict, spec: Dict) -> float:
    return 3 * forward_flops_per_sample(config, spec)


def ssd_chunk_flops_per_call(config: Dict, spec: Dict) -> float:
    """One call of `ssd_chunk`: one layer, the whole batch."""
    s = _sizes(config, spec)
    return s["batch"] * _chunk_local_flops(s)


def ssd_chunk_bytes_per_call(config: Dict, spec: Dict) -> float:
    """dt x (bf16), the running sums (float32), the scores C B^T (float32)
    and B (bf16) in; the output and the chunks' states (float32) out, once."""
    s = _sizes(config, spec)
    positions = s["seq"] * (s["inner"] * (_BF16 + _F32) + s["h"] * _F32 + s["q"] * _F32 + s["n"] * _BF16)
    return s["batch"] * (positions + s["chunks"] * s["n"] * s["inner"] * _F32)


def ssd_chunk_bwd_flops_per_call(config: Dict, spec: Dict) -> float:
    """One call of `ssd_chunk_bwd`: two products for every product of the
    forward (the gradient of each operand). Scores it rebuilds are its own
    choice and not counted."""
    return 2 * ssd_chunk_flops_per_call(config, spec)


def ssd_chunk_bwd_bytes_per_call(config: Dict, spec: Dict) -> float:
    """The forward's inputs, the scores once more (transposed) and both
    cotangents (float32) in; the gradients of dt x, the running sums, the
    scores and B (float32) out, once."""
    s = _sizes(config, spec)
    read = s["seq"] * (s["inner"] * (_BF16 + _F32) + s["h"] * _F32 + 2 * s["q"] * _F32 + s["n"] * _BF16)
    read += s["chunks"] * s["n"] * s["inner"] * _F32
    written = s["seq"] * (s["inner"] * _F32 + s["h"] * _F32 + s["q"] * _F32 + s["n"] * _F32)
    return s["batch"] * (read + written)


def attention_flops_per_call(config: Dict, spec: Dict) -> float:
    """One attention kernel's call: one layer, the whole batch, one of the
    forward, the backward's dq, the backward's dk/dv. Each has two products
    to deliver a visible pair and head: 4 * head operations."""
    s = _sizes(config, spec)
    return s["batch"] * 4 * s["hd"] * s["hq"] * s["pairs"]


def attention_bytes_per_call(config: Dict, spec: Dict) -> float:
    """q and the output (or its gradient) once, k and v once, bf16."""
    s = _sizes(config, spec)
    return s["batch"] * s["seq"] * s["hd"] * (2 * s["hq"] + 2 * s["hkv"]) * _BF16
