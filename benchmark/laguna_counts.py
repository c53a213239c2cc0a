"""Operations and bytes the `laguna-moe` family's algorithm requires, from
shapes alone; each `fn(config, spec)` as benchmark/families.py calls it.

Forward, per position and layer: the q, k, v, o projections at the layer's
own head count and the gate's; a `dense` layer's gated MLP; a `sparse`
layer's router over ALL experts, its shared expert, and the routed experts
for the rows EXPECTED here, `num_experts_per_tok * held / all` a position
(the count does not follow a run's routing). Scores and values for the
VISIBLE (query, key) pairs only: L (L + 1) / 2 a head in a `full_attention`
layer; in a `sliding_attention` layer of window W, W (W + 1) / 2 + (L - W) W
(a query sees min(i + 1, W) keys). The head and loss run at the L - 1
positions that predict. Training is three forwards and no recomputation;
nothing here knows the program's tiles, chunks, head groups or remat.

A kernel's count is of ONE call: one layer, the whole batch. Where a kind's
layers differ in head count, a call of that kind is the mean layer.
"""

from __future__ import annotations

from typing import Dict

_BF16, _F32 = 2, 4


def _sizes(config: Dict, spec: Dict) -> Dict[str, float]:
    seq, window = spec["seq_len"], min(config["sliding_window"], spec["seq_len"])
    held, everyone = config["num_experts"], config["num_experts"] * config["program"]["expert_parallel"]
    return {
        "d": config["hidden_size"], "hd": config["head_dim"], "hkv": config["num_key_value_heads"],
        "f": config["intermediate_size"], "fe": config["moe_intermediate_size"],
        "fs": config["shared_expert_intermediate_size"], "v": config["vocab_size"], "router": everyone,
        "seq": seq, "batch": spec["batch"], "experts": held,
        "pairs": {"full_attention": seq * (seq + 1) // 2,
                  "sliding_attention": window * (window + 1) // 2 + (seq - window) * window},
        # rows a sparse layer's held experts take of one sample, expected
        "rows": seq * config["num_experts_per_tok"] * held / everyone,
    }


def _layers(config: Dict):
    return list(zip(config["layer_types"], config["mlp_layer_types"], config["num_attention_heads_per_layer"]))


def visible_pairs(config: Dict, spec: Dict) -> Dict[str, int]:
    """(query, key) pairs a head sees in one row, by the layer's kind."""
    return dict(_sizes(config, spec)["pairs"])


def forward_flops_per_sample(config: Dict, spec: Dict) -> float:
    s = _sizes(config, spec)
    total = (s["seq"] - 1) * 2 * s["d"] * s["v"]
    for kind, mlp, hq in _layers(config):
        per_position = 2 * s["d"] * s["hd"] * (2 * hq + 2 * s["hkv"]) + 2 * s["d"] * hq
        if mlp == "dense":
            per_position += 2 * 3 * s["d"] * s["f"]
        else:
            per_position += 2 * s["d"] * s["router"] + 2 * 3 * s["d"] * s["fs"]
            total += 2 * 3 * s["d"] * s["fe"] * s["rows"]
        total += s["seq"] * per_position + 4 * s["hd"] * hq * s["pairs"][kind]
    return total


def train_flops_per_sample(config: Dict, spec: Dict) -> float:
    return 3 * forward_flops_per_sample(config, spec)


def _mean_heads(config: Dict, kind: str) -> float:
    heads = [hq for layer_kind, _, hq in _layers(config) if layer_kind == kind]
    return sum(heads) / len(heads)


def _attention_flops(config, spec, kind):
    """One attention kernel's call: one layer, the whole batch, one of the
    forward, the backward's dq, the backward's dk/dv. Each has two products
    to deliver a visible pair and head: 4 * head_dim operations."""
    s = _sizes(config, spec)
    return s["batch"] * 4 * s["hd"] * _mean_heads(config, kind) * s["pairs"][kind]


def _attention_bytes(config, spec, kind):
    """q and the output (or its gradient) once, k and v once, bf16."""
    s = _sizes(config, spec)
    return s["batch"] * s["seq"] * s["hd"] * (2 * _mean_heads(config, kind) + 2 * s["hkv"]) * _BF16


def full_attention_flops_per_call(config: Dict, spec: Dict) -> float:
    """A `block_attention*` call: a `full_attention` layer's."""
    return _attention_flops(config, spec, "full_attention")


def full_attention_bytes_per_call(config: Dict, spec: Dict) -> float:
    return _attention_bytes(config, spec, "full_attention")


def window_attention_flops_per_call(config: Dict, spec: Dict) -> float:
    """A `window_attention*` call: a `sliding_attention` layer's."""
    return _attention_flops(config, spec, "sliding_attention")


def window_attention_bytes_per_call(config: Dict, spec: Dict) -> float:
    """q and the output once, k and v once: a key tile two query tiles read
    is the kernel's choice and counted once."""
    return _attention_bytes(config, spec, "sliding_attention")


def grouped_matmul_flops_per_call(config: Dict, spec: Dict) -> float:
    """One pass of one sparse layer's routed products over the batch's
    expected rows: gate and up (D x 2F) and down (F x D). The forward is one
    pass, the backward's product with the transposed weights another, the
    weights' gradient (`grouped_matmul_drhs`) a third: the same count each."""
    s = _sizes(config, spec)
    return s["batch"] * 2 * 3 * s["d"] * s["fe"] * s["rows"]


def grouped_matmul_bytes_per_call(config: Dict, spec: Dict) -> float:
    """The held experts' three matrices once, each row in (D), its hidden
    (2F out, F in) and its output (D) once, bf16."""
    s = _sizes(config, spec)
    rows = s["batch"] * s["rows"]
    return (s["experts"] * 3 * s["d"] * s["fe"] + rows * (2 * s["d"] + 3 * s["fe"])) * _BF16


def _prologue_operand_bytes(config, spec):
    """q's and k's projections of the mean layer, bf16, and the two float32
    tables of a head each call reads."""
    s = _sizes(config, spec)
    heads = sum(hq for _, _, hq in _layers(config)) / config["num_hidden_layers"]
    operands = s["batch"] * s["seq"] * s["hd"] * (heads + s["hkv"]) * _BF16
    return operands, 2 * 2 * s["seq"] * s["hd"] * _F32


def qk_norm_rope_flops_per_call(config: Dict, spec: Dict) -> float:
    """A layer's two `qk_norm_rope` calls (q's and k's, counted together: a
    trace tells them apart by shape only): a norm and a rotation, about 10
    operations an element; the bytes bound holds."""
    return 10 * _prologue_operand_bytes(config, spec)[0] / _BF16


def qk_norm_rope_bytes_per_call(config: Dict, spec: Dict) -> float:
    """x in and z out, and the tables."""
    operands, tables = _prologue_operand_bytes(config, spec)
    return 2 * operands + tables


def qk_norm_rope_bwd_flops_per_call(config: Dict, spec: Dict) -> float:
    return 2 * qk_norm_rope_flops_per_call(config, spec)


def qk_norm_rope_bwd_bytes_per_call(config: Dict, spec: Dict) -> float:
    """dz and x in, dx out, and the tables."""
    operands, tables = _prologue_operand_bytes(config, spec)
    return 3 * operands + tables
