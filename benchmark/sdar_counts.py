"""Operations and bytes the `sdar-moe` family's algorithm requires, from
shapes alone; each `fn(config, spec)` as benchmark/families.py calls it.

A position is one of a sample's 2L (the noised copy, then the clean one).
Forward, per layer: the q/k/v/o projections and the router for every
position; the experts for the rows EXPECTED here, `num_experts_per_tok *
held / all` a position (the count does not follow a run's routing); scores
and values for the VISIBLE (query, key) pairs only: L * block (a block
denoises itself) + L^2 (the clean past of both halves) a sample. The head
runs at the L noised positions. Training is three forwards and no
recomputation; nothing here knows the program's tiles, chunks or remat.
"""

from __future__ import annotations

from typing import Dict

_BF16 = 2


def _sizes(config: Dict, spec: Dict) -> Dict[str, int]:
    program = config["program"]
    seq = spec["seq_len"]
    held, everyone = config["num_experts"], config["num_experts"] * program["expert_parallel"]
    return {
        "d": config["hidden_size"], "layers": config["num_hidden_layers"], "hq": config["num_attention_heads"],
        "hkv": config["num_key_value_heads"], "hd": config["head_dim"], "f": config["moe_intermediate_size"],
        "v": config["vocab_size"], "router": everyone, "seq": seq, "batch": spec["batch"],
        "pairs": seq * program["block_length"] + seq * seq,
        # rows a layer's experts take of one sample, expected
        "rows": 2 * seq * config["num_experts_per_tok"] * held / everyone,
        "experts": held,
    }


def forward_flops_per_sample(config: Dict, spec: Dict) -> float:
    s = _sizes(config, spec)
    projections = 2 * s["d"] * s["hd"] * (2 * s["hq"] + 2 * s["hkv"])
    per_position = projections + 2 * s["d"] * s["router"]
    attention = 4 * s["hd"] * s["hq"] * s["pairs"]
    experts = 2 * 3 * s["d"] * s["f"] * s["rows"]
    layer = 2 * s["seq"] * per_position + attention + experts
    return s["layers"] * layer + s["seq"] * 2 * s["d"] * s["v"]


def train_flops_per_sample(config: Dict, spec: Dict) -> float:
    return 3 * forward_flops_per_sample(config, spec)


def attention_flops_per_call(config: Dict, spec: Dict) -> float:
    """One attention kernel's call: one layer, the whole batch, one of the
    forward, the backward's dq, the backward's dk/dv. Each has two products
    to deliver a visible pair and head (scores and values; dP and dQ; dV and
    dK): 4 * head_dim operations. Scores a backward kernel rebuilds are its
    own choice and not counted."""
    s = _sizes(config, spec)
    return s["batch"] * 4 * s["hd"] * s["hq"] * s["pairs"]


def attention_bytes_per_call(config: Dict, spec: Dict) -> float:
    """q and the output (or its gradient) once, k and v once, bf16."""
    s = _sizes(config, spec)
    return s["batch"] * 2 * s["seq"] * s["hd"] * (2 * s["hq"] + 2 * s["hkv"]) * _BF16


def grouped_matmul_flops_per_call(config: Dict, spec: Dict) -> float:
    """One pass of one layer's expert products over the batch's expected
    rows: gate and up (D x 2F) and down (F x D). The forward is one pass, the
    backward's product with the transposed weights another, the weights'
    gradient (`grouped_matmul_drhs`) a third: the same count each."""
    s = _sizes(config, spec)
    return s["batch"] * 2 * 3 * s["d"] * s["f"] * s["rows"]


def grouped_matmul_bytes_per_call(config: Dict, spec: Dict) -> float:
    """The held experts' three matrices once, each row in (D), its hidden
    (2F out, F in) and its output (D) once, bf16."""
    s = _sizes(config, spec)
    rows = s["batch"] * s["rows"]
    return (s["experts"] * 3 * s["d"] * s["f"] + rows * (2 * s["d"] + 3 * s["f"])) * _BF16
