"""Typed configuration shared by every entry point.

The reference repo has no config system: ~10 architecture flags are duplicated
across three argparse blocks (/root/reference/train_stereo.py:256-264,
evaluate_stereo.py:199-207, demo.py:218-226), plus a set of hardcoded constants
(data modality, dataset roots, camera intrinsics). Here the whole surface is a
frozen dataclass tree so the same object configures the model, trainer, eval
and demo, and hashes cleanly as a static argument under `jax.jit`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

# Data modalities of the gated-stereo fork (reference core/extractor.py:140-143):
# "RGB" and "1 Passive Gated" are 3-channel, "All Gated" stacks 5 gated slices.
MODALITY_RGB = "RGB"
MODALITY_PASSIVE_GATED = "1 Passive Gated"
MODALITY_ALL_GATED = "All Gated"
MODALITIES = (MODALITY_RGB, MODALITY_PASSIVE_GATED, MODALITY_ALL_GATED)

# Correlation implementations. "reg" precomputes the full pyramid (reference
# core/corr.py:110-156); "alt" recomputes correlation on the fly per level
# (core/corr.py:64-107); "pallas" is this framework's fused TPU kernel — the
# role the "reg_cuda" CUDA extension plays in the reference (core/corr.py:31-61).
CORR_IMPLEMENTATIONS = ("reg", "alt", "pallas")

# Sharding rule presets. The rule tables live in parallel/sharding.PRESETS;
# this tuple mirrors its keys so config validation stays import-light (a
# tier-1 test asserts the two never drift).
SHARDING_PRESETS = ("dp", "spatial", "dp+spatial", "fsdp")


def input_channels(data_modality: str) -> int:
    """Encoder input channels per modality (reference core/extractor.py:140-143)."""
    if data_modality not in MODALITIES:
        raise ValueError(f"unknown data_modality {data_modality!r}; expected one of {MODALITIES}")
    return 5 if data_modality == MODALITY_ALL_GATED else 3


@dataclasses.dataclass(frozen=True)
class RAFTStereoConfig:
    """Model architecture config (reference flag table: SURVEY.md §2.4).

    Defaults reproduce the reference defaults (train_stereo.py:256-264).
    """

    # GRU hidden dims per scale, coarsest-first indexing as in the reference
    # (hidden_dims[2] is the finest scale; core/update.py:104-107). The
    # reference aliases context_dims to hidden_dims (core/raft_stereo.py:27).
    hidden_dims: Tuple[int, ...] = (128, 128, 128)
    corr_implementation: str = "reg"
    corr_levels: int = 4
    corr_radius: int = 4
    # Disparity field lives at 1/2**n_downsample resolution
    # (core/extractor.py:144,149,150; core/raft_stereo.py:58).
    n_downsample: int = 2
    n_gru_layers: int = 3
    slow_fast_gru: bool = False
    shared_backbone: bool = False
    data_modality: str = MODALITY_RGB
    # bf16 compute in encoders + GRUs; the correlation volume and lookup stay
    # fp32 (the reference keeps lookup fp32 unless using the CUDA sampler —
    # evaluate_stereo.py:227-230 explains the rounding rationale).
    mixed_precision: bool = False
    # Storage dtype of the precomputed "reg" correlation pyramid. "bfloat16"
    # halves HBM for the O(H*W^2) volume — the role the fp16 reg_cuda volume
    # plays in the reference (core/corr.py:31-61); interpolation arithmetic
    # stays fp32 either way (ops/corr.py).
    corr_dtype: str = "float32"
    # Run the feature encoder one image at a time instead of as one 2B
    # batch. Identical math and params; peak full-resolution trunk memory
    # becomes ONE image's regardless of batch — the single-chip enabler for
    # Middlebury-F inference (the multi-chip answer is H-sharding over the
    # spatial mesh axis). Two forms, chosen by batch size: B=1 chains the
    # second image on a 1e-30-scaled scalar of the first feature map (a
    # data dependency that forces XLA to free image1's trunk first;
    # measured ~1.5% faster than a 2-step scan); B>=2 scans over the image
    # stack, which reuses the body's buffers structurally.
    sequential_encoder: bool = False
    # Evaluate the encoder trunks' layer1 (and the layer2_0 entry convs) in
    # the W-space-to-depth domain for TRAIN-MODE forwards: the C=64 convs
    # half-starve the MXU's 128 contraction lanes; the 128-channel s2d
    # embedding runs the convs ~1.4x faster and — decisively — its C=128 dw
    # (kernel-gradient) convs avoid XLA's kx-minor stacked-layout pathology
    # (round-3 trace), taking the b4 recipe step 0.513 -> 0.462 s and
    # -3.2 GB HBM (round 4). Identical math (f64-exact) and identical
    # parameter tree; entering the domain is a pure reshape, leaving it
    # rides the stride-2 layer2 kernels. test_mode forwards keep the
    # direct-conv path: in the inference graph the s2d convs attract ~100 ms
    # of layout copies and lose the conv+IN-sum multi-output fusion
    # (round-4 trace — measured, not fundamental; revisit with a newer XLA).
    encoder_s2d: bool = True
    # Rematerialize each GRU iteration in the backward pass (jax.checkpoint
    # on the scanned body). Training memory drops from O(iters * per-iter
    # activations) to O(iters * carry) at the cost of one extra forward per
    # iteration in backward. The reference training recipe (global batch 8
    # over 2 GPUs = batch 4 per device, 22 iterations, 320x720 crops;
    # reference README.md:109-113) fits a 16 GB v5e chip at batch 4 ONLY
    # with this on. No effect on inference (nothing to rematerialize
    # without a backward pass).
    remat_iterations: bool = True
    # Fused Pallas encoder kernels (ops/encoder_pallas.py): stem-norm +
    # layer1 resblocks as implicit-GEMM kernels with the
    # InstanceNorm/FrozenBN epilogues and residual joins computed
    # in-register, plus the corr volume+pyramid+pad built in one kernel
    # (ops/corr_pallas.fused_pyramid_state, "pallas" corr only).
    # TEST-MODE forwards only (the kernels define no VJP — training keeps
    # the XLA formulation); applies under the same conditions as the s2d
    # domain (even W at stem resolution, instance/batch norm). On the CPU
    # backend the kernels run in the Pallas interpreter (ops/pallas_mode.py)
    # — fine for tier-1 parity tests, pathologically slow at full
    # resolution. Every kernel of this strategy compiles for v5e at
    # Middlebury-F width, bf16 storage included (tests/test_chip_compile.py).
    # Measured as `"fused_encoder": true` in a configuration's `program`
    # group, cell `full-offline-middlebury-f`: PERF.md section 6, "Levers".
    fused_encoder: bool = False
    # Scalar-prefetch windowed correlation lookup ("pallas" corr only): the
    # per-row integer window starts derived from the lookup coordinates ride
    # a PrefetchScalarGridSpec scalar operand, so each program DMAs only a
    # fixed window of 128-lane pyramid tiles around where its taps land
    # instead of every level's full padded row. Bit-identical to the dense
    # kernel on every input (a computed fits-predicate lax.cond-falls back to
    # it for coordinate fields too rough to window). TEST-MODE forwards only
    # (no VJP — training keeps pallas_corr_lookup_padded); on the CPU backend
    # the kernel runs in the Pallas interpreter for the tier-1 parity tests.
    # Measured as `"prefetch_lookup": true` in a configuration's `program`
    # group, cell `full-offline-middlebury-f`: PERF.md section 6, "Levers".
    prefetch_lookup: bool = False
    # Fused ConvGRU gate tail + motion-encoder concat (ops/gru_tail_pallas.py):
    # ONE Pallas call per cell computing sigmoid/tanh/blend at the scan-carry
    # materialization boundary, plus one call writing the 128ch motion concat
    # — two calls a cell and iteration where XLA emits several elementwise
    # passes. TEST-MODE forwards only (no VJP; training path proven
    # untouched by the exact-gradient-equality test). Measured as
    # `"fused_gru_tail": true` in a configuration's `program` group, cell
    # `full-offline-middlebury-f`: PERF.md section 6, "Levers".
    fused_gru_tail: bool = False
    # With remat_iterations on, KEEP across the backward what is small to
    # hold and dear to rebuild ("save_only_these_names" checkpoint policy,
    # models/raft_stereo.REMAT_SAVED_NAMES): the correlation lookup's taps
    # (the gather kernel), and the pre-activation sums of the three GRU gates
    # at every scale, so the recompute pass re-runs no GRU convolution — only
    # the gates' elementwise nonlinearities, the motion encoder, the flow
    # head and the cross-scale pool / interpolation. Cost, bf16, per
    # iteration and sample: the taps (H/2^K * W/2^K * levels*(2r+1), padded
    # to 128 lanes) plus three hidden-state-sized tensors per GRU scale; at
    # the reference recipe (22 iters, batch 4, 320x720 crops, K=2) 0.32 GB
    # for the taps and 1.13 GB measured for the sums (1.36 GB by padded
    # shape), for 25 ms of a 431 ms step (PERF.md section 6, PR 28). Off:
    # plain remat, everything recomputed — the switch for a geometry that
    # does not fit.
    remat_save_corr: bool = True
    # Emit `with_sharding_constraint` on the correlation pyramid and the GRU
    # hidden state, H rows over the mesh's spatial axis
    # (parallel/sharding.constrain_spatial). Set by the sharding engine when
    # a spatial preset is active — not a CLI flag. Lives on the MODEL config
    # so the choice is part of every jit cache key: a constrained and an
    # unconstrained graph can never share a trace. No effect on params or
    # math; identity when False (the default — all legacy graphs unchanged).
    spatial_constraints: bool = False

    @property
    def context_dims(self) -> Tuple[int, ...]:
        return self.hidden_dims

    @property
    def in_channels(self) -> int:
        return input_channels(self.data_modality)

    @property
    def downsample_factor(self) -> int:
        return 2**self.n_downsample

    @property
    def corr_channels(self) -> int:
        """Motion-encoder corr input planes: levels * (2r+1) (core/update.py:69)."""
        return self.corr_levels * (2 * self.corr_radius + 1)

    def __post_init__(self):
        if self.corr_implementation not in CORR_IMPLEMENTATIONS:
            raise ValueError(
                f"corr_implementation {self.corr_implementation!r} not in {CORR_IMPLEMENTATIONS}"
            )
        if not 1 <= self.n_gru_layers <= 3:
            raise ValueError("n_gru_layers must be in [1, 3]")
        if len(self.hidden_dims) != 3:
            raise ValueError("hidden_dims must have 3 entries (coarse, mid, fine)")
        if self.data_modality not in MODALITIES:
            raise ValueError(f"unknown data_modality {self.data_modality!r}")
        if self.corr_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"corr_dtype must be float32 or bfloat16, got {self.corr_dtype!r}")


@dataclasses.dataclass(frozen=True)
class SDARMoEConfig:
    """The second model family, `sdar-moe`: a routed-expert decoder
    (`model_type: sdar_moe`, a Qwen3-MoE block) trained by block diffusion
    (BD3-LM, arXiv:2503.09573). Key names are the published `config.json`'s;
    `from_hf_config` reads such a file. Defaults are the 30B-A3B release.

    The counts may be ONE CHIP'S SHARE of a deployment that divides every
    layer over `expert_parallel` chips: `num_experts` is the experts HELD
    here (the router still scores `num_experts * expert_parallel` and picks
    `num_experts_per_tok` among all of them; this chip holds experts
    `expert_shard * num_experts ...`), and `vocab_size` the vocabulary rows
    held here (ids, logits and loss are over that slice). What absent experts
    would add is left out; nothing stands in for the exchange."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    # -- the chip's share --
    expert_parallel: int = 1
    expert_shard: int = 0
    # -- block diffusion --
    block_length: int = 4
    mask_token_id: int = 151935
    # -- program --
    mixed_precision: bool = True  # bf16 compute, float32 parameters
    remat_layers: bool = True
    # Static bounds of the expert layer (ops/grouped_matmul.py): positions
    # are walked in chunks of `moe_chunk`, each with row buffers for the
    # worst case (every choice of every position held here), so no token is
    # dropped at any imbalance and no buffer grows with the batch. Only the
    # live tiles of a buffer are computed or copied (ops/tile_rows.py).
    moe_chunk: int = 4096
    moe_tile_rows: int = 128
    attention_tile: int = 512
    # Positions a pass of the output head and loss holds logits for.
    loss_chunk: int = 4096

    @property
    def router_width(self) -> int:
        return self.num_experts * self.expert_parallel

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if not 0 <= self.expert_shard < self.expert_parallel:
            raise ValueError(f"expert_shard {self.expert_shard} not in [0, {self.expert_parallel})")
        if self.num_experts_per_tok > self.router_width:
            raise ValueError("num_experts_per_tok exceeds the router's width")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is not a row of the {self.vocab_size} held")

    @classmethod
    def from_hf_config(cls, published: Dict[str, Any], **program) -> "SDARMoEConfig":
        """From a `config.json`-shaped dict (keys this class does not model
        are ignored) and the program's own keys."""
        names = {f.name for f in dataclasses.fields(cls)}
        merged = {k: v for k, v in {**published, **program}.items() if k in names}
        merged.setdefault("mask_token_id", merged.get("vocab_size", cls.vocab_size) - 1)
        return cls(**merged)


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The third model family, `granite-hybrid` (`model_type:
    granitemoehybrid` with no routed experts: IBM Granite 4.0-H): Mamba-2
    layers and grouped-query attention layers in the order `layer_types`
    gives, each followed by a dense gated MLP, trained on the plain
    next-token loss. Key names are the published `config.json`'s;
    `from_hf_config` reads such a file. Defaults are the 4.0-H Micro release.

    `vocab_size` may be the vocabulary rows HELD here (ids, the tied head,
    logits and loss are over that slice) and `layer_types` the layers of one
    pipeline stage; the loss is then taken at the stage's output."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = tuple(
        "attention" if i % 10 == 5 else "mamba" for i in range(40))
    intermediate_size: int = 8192  # the gated MLP's (`shared_intermediate_size`)
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # -- program --
    mixed_precision: bool = True  # bf16 compute, float32 parameters
    remat_layers: bool = True
    attention_tile: int = 512
    # Positions a pass of the output head and loss holds logits for.
    loss_chunk: int = 4096

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - {"mamba", "attention"}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(unknown) or 'nothing'}; a layer is 'mamba' or 'attention'")
        if self.num_attention_heads % self.num_key_value_heads or self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size / num_attention_heads / num_key_value_heads do not divide")
        if self.mamba_d_inner != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head must be mamba_expand * hidden_size")
        if self.mamba_n_groups != 1:
            raise NotImplementedError("the scan shares B and C among all heads (mamba_n_groups 1)")

    @classmethod
    def from_hf_config(cls, published: Dict[str, Any], **program) -> "GraniteHybridConfig":
        """From a `config.json`-shaped dict (keys this class does not model
        are ignored; routed experts are refused) and the program's own keys."""
        if published.get("num_local_experts", 0) or published.get("num_experts_per_tok", 0):
            raise NotImplementedError("granite-hybrid: routed experts (num_local_experts > 0) are not modelled")
        if published.get("position_embedding_type", "nope") != "nope":
            raise NotImplementedError("granite-hybrid: attention carries no positional embedding (nope) only")
        names = {f.name for f in dataclasses.fields(cls)}
        merged = {k: v for k, v in {**published, **program}.items() if k in names}
        if "shared_intermediate_size" in published:
            merged["intermediate_size"] = published["shared_intermediate_size"]
        if "layer_types" in merged and "num_hidden_layers" in published:
            if len(merged["layer_types"]) != published["num_hidden_layers"]:
                raise ValueError("layer_types does not name num_hidden_layers layers")
        return cls(**merged)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The fourth model family, `laguna-moe` (`model_type: laguna`: poolside
    Laguna): a routed-expert decoder on the plain next-token loss whose
    layers differ in kind. `layer_types` gives each layer's attention
    (`full_attention`: causal; `sliding_attention`: causal under a window of
    `sliding_window` keys), `num_attention_heads_per_layer` its query heads,
    `mlp_layer_types` its second half (`dense`: a gated MLP of
    `intermediate_size`; `sparse`: routed experts beside one shared expert).
    Every attention has a per-head sigmoid gate on its output (the published
    `gating`; no other value is modelled, so it is no field), q
    and k normed over the head, and the rotary embedding of its kind
    (`rope_full` / `rope_sliding`, the published `rope_parameters`' groups as
    sorted (key, value) pairs: YaRN or default, over `partial_rotary_factor`
    of the head). The router scores by sigmoid, renormalises its
    `num_experts_per_tok` choices and scales the routed sum by
    `moe_routed_scaling_factor`. Key names are the published `config.json`'s;
    `from_hf_config` reads such a file. Defaults are the XS.2 release.

    The counts may be ONE CHIP'S SHARE, as `SDARMoEConfig`'s: `num_experts`
    the experts HELD here of `num_experts * expert_parallel` (the router
    scores them all), `vocab_size` the vocabulary rows held, and the three
    per-layer lists the layers of one pipeline stage; the loss is then taken
    at the stage's output. Attention and the shared expert are whole on
    every chip."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    layer_types: Tuple[str, ...] = tuple(
        "full_attention" if i % 4 == 0 else "sliding_attention" for i in range(40))
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 39
    num_attention_heads_per_layer: Tuple[int, ...] = tuple(48 if i % 4 == 0 else 64 for i in range(40))
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_full: Tuple[Tuple[str, Any], ...] = (
        ("attention_factor", 1.4158883083359672), ("beta_fast", 64), ("beta_slow", 1), ("factor", 64),
        ("original_max_position_embeddings", 4096), ("partial_rotary_factor", 0.5), ("rope_theta", 500000),
        ("rope_type", "yarn"))
    rope_sliding: Tuple[Tuple[str, Any], ...] = (
        ("partial_rotary_factor", 1), ("rope_theta", 10000), ("rope_type", "default"))
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    # -- the chip's share --
    expert_parallel: int = 1
    expert_shard: int = 0
    # -- program (as `SDARMoEConfig`'s) --
    mixed_precision: bool = True  # bf16 compute, float32 parameters
    remat_layers: bool = True
    moe_chunk: int = 4096
    moe_tile_rows: int = 128
    attention_tile: int = 512
    loss_chunk: int = 4096

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def router_width(self) -> int:
        return self.num_experts * self.expert_parallel

    def rope(self, kind: str) -> Dict[str, Any]:
        """The rotary parameters of a layer's kind."""
        return dict(self.rope_full if kind == "full_attention" else self.rope_sliding)

    def __post_init__(self):
        for name in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        for name in ("rope_full", "rope_sliding"):
            object.__setattr__(self, name, tuple(sorted(dict(getattr(self, name)).items())))
        layers = len(self.layer_types)
        if not layers or len(self.mlp_layer_types) != layers or len(self.num_attention_heads_per_layer) != layers:
            raise ValueError("layer_types, mlp_layer_types and num_attention_heads_per_layer name the same layers")
        if set(self.layer_types) - {"full_attention", "sliding_attention"}:
            raise ValueError(f"layer_types holds {sorted(set(self.layer_types))}")
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"mlp_layer_types holds {sorted(set(self.mlp_layer_types))}")
        if any(h % self.num_key_value_heads for h in self.num_attention_heads_per_layer):
            raise ValueError("a layer's query heads must be a multiple of num_key_value_heads")
        if not 0 <= self.expert_shard < self.expert_parallel:
            raise ValueError(f"expert_shard {self.expert_shard} not in [0, {self.expert_parallel})")
        if self.num_experts_per_tok > self.router_width:
            raise ValueError("num_experts_per_tok exceeds the router's width")
        for kind in ("full_attention", "sliding_attention"):
            rope = self.rope(kind)
            if rope.get("rope_type", "default") not in ("default", "yarn"):
                raise NotImplementedError(f"laguna: rope_type {rope['rope_type']!r} is neither default nor yarn")
            if int(self.head_dim * rope.get("partial_rotary_factor", 1)) % 2:
                raise ValueError("the rotary dimension must be even")

    @classmethod
    def from_hf_config(cls, published: Dict[str, Any], **program) -> "LagunaConfig":
        """From a `config.json`-shaped dict (keys this class does not model
        are ignored; a gate other than per head, a softcapped router, router
        weights on the input and an attention bias are refused) and the
        program's own keys."""
        if published.get("gating", True) not in (True, "per-head"):
            raise NotImplementedError(f"laguna: gating {published['gating']!r} is not the per-head gate")
        if published.get("moe_router_logit_softcapping", 0) or published.get("moe_apply_router_weight_on_input"):
            raise NotImplementedError("laguna: a softcapped router / router weights on the input are not modelled")
        if published.get("attention_bias") or published.get("tie_word_embeddings"):
            raise NotImplementedError("laguna: no bias in attention, and an untied head")
        names = {f.name for f in dataclasses.fields(cls)}
        merged = {k: v for k, v in {**published, **program}.items() if k in names}
        groups = published.get("rope_parameters", {})
        for field, kind in (("rope_full", "full_attention"), ("rope_sliding", "sliding_attention")):
            if kind in groups:
                merged[field] = tuple(sorted(groups[kind].items()))
        if "layer_types" in merged and "num_hidden_layers" in published:
            if len(merged["layer_types"]) != published["num_hidden_layers"]:
                raise ValueError("layer_types does not name num_hidden_layers layers")
        return cls(**merged)


# `model_type` of a published config.json -> the family's config class
# (`cli --token_config` picks the family by it).
TOKEN_FAMILIES = {"sdar_moe": SDARMoEConfig, "granitemoehybrid": GraniteHybridConfig, "laguna": LagunaConfig}

ModelConfig = Union[RAFTStereoConfig, SDARMoEConfig, GraniteHybridConfig, LagunaConfig]


@dataclasses.dataclass(frozen=True)
class CameraConfig:
    """Gated-stereo rig intrinsics, hardcoded in the reference
    (core/utils/frame_utils.py:127-128, demo.py:21-22)."""

    focal_px: float = 2840.562197
    baseline_m: float = 658.280549 / 2840.562197
    # Lidar-MAE valid depth range in meters (demo.py:28-29).
    min_depth_m: float = 3.0
    max_depth_m: float = 200.0


@dataclasses.dataclass(frozen=True)
class AugmentConfig:
    """Data-augmentation knobs (reference train_stereo.py:267-271 plus the
    aug-params assembly in core/stereo_datasets.py:500-514)."""

    crop_size: Tuple[int, int] = (320, 720)
    # Reference argparse default is --spatial_scale 0 0 (train_stereo.py:270);
    # the README training recipe uses `--spatial_scale -0.2 0.4`.
    min_scale: float = 0.0
    max_scale: float = 0.0
    do_flip: Optional[str] = None  # None | "h" (stereo swap) | "hf" | "v"
    yjitter: bool = True
    saturation_range: Optional[Tuple[float, float]] = None
    img_gamma: Optional[Tuple[float, float]] = None


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training-loop config (reference train_stereo.py:234-272)."""

    # One of the model families: the trainer takes how to initialise,
    # what a batch holds and the loss from it (train/families.py).
    model: ModelConfig = dataclasses.field(default_factory=RAFTStereoConfig)
    augment: AugmentConfig = dataclasses.field(default_factory=AugmentConfig)
    camera: CameraConfig = dataclasses.field(default_factory=CameraConfig)

    name: str = "raft-stereo"
    batch_size: int = 6
    train_datasets: Tuple[str, ...] = ("sceneflow",)
    lr: float = 2e-4
    num_steps: int = 100_000
    train_iters: int = 16
    valid_iters: int = 32
    wdecay: float = 1e-5
    # Loss (train_stereo.py:35-70).
    loss_gamma: float = 0.9
    max_flow: float = 700.0
    grad_clip_norm: float = 1.0
    seed: int = 1234
    # Checkpoint cadence (train_stereo.py:172).
    checkpoint_every: int = 500
    # Checkpoint retention (orbax CheckpointManagerOptions): keep the newest
    # `max_to_keep` steps; with `keep_period` set, ADDITIONALLY keep every
    # step divisible by it forever — the sparse long-horizon trail that lets
    # a 100k-step run fall back weeks, not minutes, when late checkpoints
    # turn out corrupt or the run silently diverged.
    max_to_keep: int = 5
    keep_period: Optional[int] = None
    # Crash-consistent auto-resume (utils/checkpoints.py, README
    # "Operations"): at startup, scan this run's checkpoint root, restore
    # the newest step whose integrity manifest verifies (walking past — and
    # quarantining — torn/corrupt steps), and continue the FULL run state
    # (data-stream position, quarantine set, failure-budget and NaN
    # counters). With no checkpoints present the run starts fresh from step
    # 0 — so "rerun the same command" is always the correct recovery.
    auto_resume: bool = False
    # In-training validation cadence (the reference carries this hook at
    # validation_frequency=500, train_stereo.py:172,208-210; the call itself
    # is commented out there — here it runs). Active when the trainer is
    # given a validate_fn (e.g. via the train CLI's --valid_datasets).
    validate_every: int = 500
    checkpoint_dir: str = "checkpoints"
    restore_ckpt: Optional[str] = None
    root_dataset: Optional[str] = None
    log_every: int = 100
    # Device mesh: (data, spatial). spatial>1 shards image rows across chips —
    # this framework's sequence/context-parallel axis (the 1D-per-row corr
    # structure makes row sharding communication-free at lookup time).
    mesh_shape: Tuple[int, int] = (1, 1)
    # Sharding rule preset (parallel/sharding.PRESETS): "dp" replicates
    # state and shards the batch over the data axis (the legacy layout,
    # bit-identical); "spatial"/"dp+spatial" additionally constrain the corr
    # pyramid + GRU hidden state over the spatial axis. The preset picks the
    # RULES; mesh_shape picks the axis sizes (a spatial preset on a (n, 1)
    # mesh is valid but inert).
    sharding_rules: str = "dp"
    num_workers: int = 4
    # "thread" shares memory (native decode core releases the GIL); "process"
    # is the reference's worker model (core/stereo_datasets.py:541-542) and
    # scales the numpy-heavy augment path past the GIL on many-core hosts.
    worker_type: str = "thread"
    # Logging/profiling: metrics (TensorBoard + JSONL) land in log_dir;
    # profile_steps > 0 captures a jax.profiler device trace for that many
    # steps after warmup into <log_dir>/profile (obs.profile).
    log_dir: str = "runs"
    profile_steps: int = 0

    # --- resilience (utils/resilience.py; README "Operations") ---
    # NaN/Inf loss or grad-norm policy: "raise" fails fast on detection;
    # "skip" drops the poisoned update on device and keeps going; "rollback"
    # additionally restores the last good checkpoint after nan_patience
    # consecutive bad steps and re-seeds the data stream. Under skip/rollback
    # the update is applied conditionally INSIDE the jitted step, so params
    # and opt_state can never absorb a non-finite update regardless of how
    # promptly the host notices.
    nan_policy: str = "raise"
    # Consecutive non-finite steps before skip escalates to an error /
    # rollback restores the last good checkpoint.
    nan_patience: int = 10
    # Host-side detection cadence: non-finite flags are fetched in one bulk
    # device_get every this many steps. None (the default) resolves per
    # backend at config-finalize time (finalize_train_config): 1 on CPU
    # (fetches are free) vs 25 on TPU, where each fetch is a device-to-host
    # sync that stalls async dispatch. The device-side update skip is
    # unaffected by this cadence.
    nan_check_every: Optional[int] = None
    # Pod-coordination cadence (parallel/coordination.py): every this many
    # steps each host's resilience flags (stop request, non-finite verdict,
    # rollback wish, dropped-sample counts) are all-reduced so every process
    # takes the identical branch at the identical step. None resolves to the
    # finalized nan_check_every, aligning agreement boundaries with the
    # non-finite drain (a stop/rollback is then acted on with zero extra
    # delay). Irrelevant single-host: coordination is a no-op fast path.
    coord_interval: Optional[int] = None
    # Step watchdog (utils/resilience.py StepWatchdog): if a step boundary —
    # including the collective checkpoint save — takes longer than this,
    # dump all-thread stack traces, write run_report.json with
    # stop_cause="watchdog", and exit with the watchdog exit code instead of
    # hanging the pod forever. 0 disables (the default: step time varies
    # wildly across configs, so an always-on default would be a flake
    # machine). Size it at ~10x the steady-state step time.
    step_timeout_s: float = 0.0
    # Extra allowance on the FIRST watchdog interval: step 1 includes the
    # XLA compile of the train step, which can exceed any sane steady-state
    # step_timeout_s by orders of magnitude.
    watchdog_grace_s: float = 300.0
    # Retry-with-backoff (utils/retry.py) on checkpoint save/restore I/O:
    # attempts and base backoff delay (jittered exponential).
    io_retries: int = 3
    io_backoff: float = 0.5
    # Loader per-sample failure policy: "raise" aborts the epoch on a decode
    # failure (reference behavior); "quarantine" retries the sample
    # sample_retries times, then quarantines the index, substitutes a
    # resample, and counts it — hard-failing only past failure_budget
    # (fraction of attempted samples dropped).
    sample_policy: str = "quarantine"
    sample_retries: int = 2
    failure_budget: float = 0.05
    # Install SIGTERM/SIGINT handlers during fit() for graceful preemption
    # (stop at the next step boundary + final synchronous checkpoint).
    handle_signals: bool = True

    # --- jit hygiene (utils/jit_hygiene.py; README "Developer tooling") ---
    # Strict mode runs the training loop under jax.transfer_guard("disallow")
    # — any implicit device<->host transfer raises at the offending line,
    # while the explicit fetch points (device_get in the nan-flag drain and
    # metrics flush, device_put in shard_batch) and the whitelisted I/O
    # windows (checkpoint save, validation, rollback) stay legal — and
    # hard-fails the run on ANY XLA compile after the first recompile_grace
    # steps. Off by default in production (a guard trip aborts the run);
    # tier-1 proves every shipped configuration runs strict-clean.
    strict_mode: bool = False
    # Steps from fit() start during which compilation is expected (the train
    # step's trace+compile, nan-policy anchor save). After this window a
    # compile outside a whitelisted phase means some input's
    # shape/dtype/static key churns per step — the silent throughput killer
    # strict mode exists to catch.
    recompile_grace: int = 2

    # --- training I/O spine (train/io_spine.py, data/prefetch.py; README
    # "Operations") ---
    # Run the post-snapshot half of each checkpoint save (orbax flush +
    # run_state/manifest sidecars) on a background thread. The device→host
    # snapshot stays inside the step-boundary whitelist window, at most one
    # commit is in flight (a barrier joins it before the next save, a
    # rollback restore, and the final synchronous exit save), and the
    # manifest is still written LAST — so a SIGKILL mid-commit leaves a torn
    # step that auto-resume/fsck skip, exactly as with sync saves.
    async_checkpoint: bool = False
    # Stage batch N+1 on device (through the sharding engine's place_batch)
    # while step N runs, via a maxsize-1 double buffer around the loader.
    # Zero new executables; batch-exact resume is preserved (the loader
    # cursor checkpointed is the one matching the batch being stepped on).
    device_prefetch: bool = False

    # --- observability (obs/ package; README "Observability") ---
    # Prometheus text-exposition sidecar: > 0 starts a stdlib HTTP server on
    # this port during fit() serving GET /metrics (step-time/data-wait
    # histograms, non-finite/step counters, save-boundary device-memory
    # gauges). 0 disables (the default — training boxes rarely want a
    # listening socket without asking).
    metrics_port: int = 0
    # Flight-recorder ring capacity (obs/trace.py): the last N spans/events
    # dumped as <log_dir>/flight_recorder.json by the watchdog, non-finite
    # events, and every fit() exit path. 0 disables recording entirely.
    flight_recorder_events: int = 256
    # Persistent XLA compilation cache directory (`train
    # --compilation_cache_dir`): compiled programs are written here and
    # reloaded by later processes, so a restart (preemption recovery,
    # rolling config-identical relaunch) skips the minutes-long
    # trace+compile. None = the fixed .jax_cache/ in the checkout; the
    # JAX_COMPILATION_CACHE_DIR environment variable wins over both
    # (utils/compile_cache.py). The serving-side analogue is
    # ServeConfig.aot_cache_dir.
    compilation_cache_dir: Optional[str] = None

    def __post_init__(self):
        from raft_stereo_tpu.utils.resilience import NAN_POLICIES, SAMPLE_POLICIES

        if self.nan_policy not in NAN_POLICIES:
            raise ValueError(f"nan_policy {self.nan_policy!r} not in {NAN_POLICIES}")
        if self.sample_policy not in SAMPLE_POLICIES:
            raise ValueError(
                f"sample_policy {self.sample_policy!r} not in {SAMPLE_POLICIES}"
            )
        if self.nan_patience < 1:
            raise ValueError(f"nan_patience must be >= 1, got {self.nan_patience}")
        if self.nan_check_every is not None and self.nan_check_every < 1:
            raise ValueError(f"nan_check_every must be >= 1, got {self.nan_check_every}")
        if self.coord_interval is not None and self.coord_interval < 1:
            raise ValueError(f"coord_interval must be >= 1, got {self.coord_interval}")
        if self.step_timeout_s < 0:
            raise ValueError(f"step_timeout_s must be >= 0, got {self.step_timeout_s}")
        if self.max_to_keep < 1:
            raise ValueError(f"max_to_keep must be >= 1, got {self.max_to_keep}")
        if self.keep_period is not None and self.keep_period < 1:
            raise ValueError(f"keep_period must be >= 1, got {self.keep_period}")
        if self.io_retries < 1:
            raise ValueError(f"io_retries must be >= 1, got {self.io_retries}")
        if self.recompile_grace < 0:
            raise ValueError(
                f"recompile_grace must be >= 0, got {self.recompile_grace}"
            )
        if not 0.0 <= self.failure_budget <= 1.0:
            raise ValueError(
                f"failure_budget must be in [0, 1], got {self.failure_budget}"
            )
        if self.sharding_rules not in SHARDING_PRESETS:
            raise ValueError(
                f"sharding_rules {self.sharding_rules!r} not in {SHARDING_PRESETS}"
            )
        if not 0 <= self.metrics_port <= 65535:
            raise ValueError(
                f"metrics_port must be in [0, 65535], got {self.metrics_port}"
            )
        if self.flight_recorder_events < 0:
            raise ValueError(
                "flight_recorder_events must be >= 0, "
                f"got {self.flight_recorder_events}"
            )


# Per-backend default for the host-side non-finite detection cadence:
# every fetch is a device-to-host sync, which is free on CPU but on a TPU
# makes the host wait for the step in flight and ends async dispatch — so
# check every step where it costs nothing and every ~25 steps where it
# doesn't.
NAN_CHECK_EVERY_BACKEND_DEFAULTS = {"cpu": 1, "tpu": 25}
_FINALIZE_LOGGED = False


def finalize_train_config(config: "TrainConfig") -> "TrainConfig":
    """Resolve runtime-dependent defaults (None fields) against the active
    JAX backend. Idempotent — a finalized config passes through unchanged —
    and called by the Trainer, so hand-built configs work without an
    explicit call. Logs the resolution once per process at first use."""
    global _FINALIZE_LOGGED
    if config.nan_check_every is not None and config.coord_interval is not None:
        return config
    import logging

    nan_check = config.nan_check_every
    if nan_check is None:
        import jax

        backend = jax.default_backend()
        nan_check = NAN_CHECK_EVERY_BACKEND_DEFAULTS.get(backend, 1)
        if not _FINALIZE_LOGGED:
            logging.getLogger(__name__).info(
                "nan_check_every resolved to %d for backend %r "
                "(per-backend default; override with --nan_check_every)",
                nan_check,
                backend,
            )
            _FINALIZE_LOGGED = True
    coord = config.coord_interval if config.coord_interval is not None else nan_check
    return dataclasses.replace(config, nan_check_every=nan_check, coord_interval=coord)


@dataclasses.dataclass(frozen=True)
class EvalConfig:
    """Evaluation config (reference evaluate_stereo.py:192-242)."""

    model: RAFTStereoConfig = dataclasses.field(default_factory=RAFTStereoConfig)
    camera: CameraConfig = dataclasses.field(default_factory=CameraConfig)
    dataset: str = "middlebury_F"
    valid_iters: int = 32
    restore_ckpt: Optional[str] = None
    root_dataset: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class VideoConfig:
    """Streaming/video stereo session policy (video/ package; ROADMAP open
    item 4).

    A stream session carries the previous frame's low-res disparity flow and
    warm-starts the next frame's refinement through the `flow_init` path
    (models/anytime.py AnytimePrelude / models/raft_stereo.py), so warm frames
    reach cold-start EPE in far fewer GRU iterations. A host-side EPE proxy —
    photometric warp error of the candidate `flow_init` on the NEW frame pair,
    at 1/4 res — gates the warm start: when the prior flow explains the new
    frame dramatically worse than it explained its own frame (scene cut,
    teleporting camera), the session resets to cold-start instead of
    diverging. The gate is pure numpy on already-host-resident images: it
    adds no executables and cannot recompile, preserving the serving tier's
    zero-post-warmup-recompile contract.
    """

    # Warm-start at all. False degrades every frame to cold-start (A/B knob).
    warm_start: bool = True
    # Also carry the ConvGRU hidden state across frames (host-side swap of
    # state["net"] between prelude and first chunk — no new executables).
    carry_hidden: bool = False
    # GRU iterations per jitted chunk for the standalone StreamSession.
    # Serving streams use ServeConfig.chunk_iters; __post_init__ there
    # enforces the two agree so one warmed executable set drives both.
    chunk_iters: int = 4
    # Refinement budget for cold frames (frame 0, post-reset frames).
    cold_iters: int = 32
    # Refinement budget for warm-started frames — the whole point: fewer
    # iterations at equal EPE (`video.warm_cold_parity` measures how many).
    warm_iters: int = 8
    # Reset gate: reset when the candidate flow's warp error on the new pair
    # exceeds `reset_error_ratio` x the error the SAME flow achieved on its
    # own frame, AND exceeds `reset_error_floor` (absolute, mean |I1 - warp|
    # in [0,255] intensity units — the floor keeps near-perfect warps from
    # tripping the ratio on noise). Continuous video sits at ratio ~1; scene
    # cuts land 3-10x depending on texture scale, hence 2.5.
    reset_error_ratio: float = 2.5
    reset_error_floor: float = 4.0

    def __post_init__(self):
        if self.chunk_iters < 1:
            raise ValueError(f"chunk_iters must be >= 1, got {self.chunk_iters}")
        if self.cold_iters < 1:
            raise ValueError(f"cold_iters must be >= 1, got {self.cold_iters}")
        if self.warm_iters < 1:
            raise ValueError(f"warm_iters must be >= 1, got {self.warm_iters}")
        if self.warm_iters > self.cold_iters:
            raise ValueError(
                f"warm_iters ({self.warm_iters}) must be <= cold_iters "
                f"({self.cold_iters}) — warm start exists to spend FEWER "
                "iterations"
            )
        if self.reset_error_ratio <= 0:
            raise ValueError(
                f"reset_error_ratio must be > 0, got {self.reset_error_ratio}"
            )
        if self.reset_error_floor < 0:
            raise ValueError(
                f"reset_error_floor must be >= 0, got {self.reset_error_floor}"
            )


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-tier config (serving/ package; ROADMAP open item 2).

    Every (bucket, batch) combination listed here is compiled at boot —
    admission maps a request onto the smallest bucket that fits, so request
    handling never compiles. Refinement runs in fixed `chunk_iters` jitted
    chunks; `max_iters` is rounded UP to a whole number of chunks (the chunk
    executable is the unit of work between deadline checks).
    """

    model: RAFTStereoConfig = dataclasses.field(default_factory=RAFTStereoConfig)
    # Padded (H, W) shape buckets, each a multiple of `divis_by`. Requests
    # are admitted into the smallest bucket that fits both dimensions;
    # larger inputs are rejected (HTTP 413 at the service front).
    buckets: Tuple[Tuple[int, int], ...] = ((384, 512), (512, 768))
    # Batch sizes warmed per bucket: 1, 2, 4, ... up to max_batch. The
    # batcher pads a partial batch up to the nearest warmed size.
    max_batch: int = 4
    # GRU iterations per jitted chunk — the deadline-check granularity.
    chunk_iters: int = 4
    # Refinement budget when a request doesn't hit its deadline first.
    max_iters: int = 32
    # Default per-request deadline; requests may override. 0 disables.
    deadline_ms: float = 0.0
    # How long the batcher waits for a partial batch to fill before
    # dispatching it anyway.
    batch_window_ms: float = 2.0
    # Padded shapes must divide by 32: the eval convention (evaluate.py) —
    # 1/4-res disparity + three 1/8..1/32 context scales below it.
    divis_by: int = 32
    host: str = "127.0.0.1"
    port: int = 8080
    restore_ckpt: Optional[str] = None
    # Sharding preset for the warmed executables (parallel/sharding.PRESETS).
    # "dp" keeps the legacy single-device jits; "spatial"/"dp+spatial" warm
    # H-sharded executables over all visible devices so full-res batched
    # buckets fit (the corr volume splits linearly across chips).
    sharding_rules: str = "dp"
    # Streaming video support. None = plain per-request serving. Set to a
    # VideoConfig to admit stream sessions (`submit_stream` / HTTP
    # "stream_id"): the engine additionally warms the flow_init prelude
    # variant per (bucket, batch) so warm-started frames reuse the compile
    # cache with zero new recompiles.
    video: Optional[VideoConfig] = None
    # Max live stream sessions; least-recently-used sessions beyond this are
    # evicted (their next frame simply cold-starts).
    max_streams: int = 1024
    # Fault lifecycle (serving/lifecycle.py). Consecutive batch failures:
    # `breaker_degrade_after` of them mark the service degraded (still
    # admitting — probation traffic is the recovery path), `breaker_fail_after`
    # trip the breaker to failed (submits shed with 503 until a checkpoint
    # swap or restart). `breaker_probation` consecutive successes take a
    # degraded service back to healthy.
    breaker_degrade_after: int = 2
    breaker_fail_after: int = 5
    breaker_probation: int = 2
    # Per-batch hang watchdog: if a refinement chunk produces no heartbeat
    # for this long, every thread's stack is dumped and the service goes
    # `failed` (the process stays up to answer /healthz). 0 disables. Size
    # it to several times the largest warmed chunk estimate.
    hang_timeout_s: float = 0.0
    # Engine replicas, one per local device (serving/fleet.EngineFleet):
    # each replica holds its own committed copy of the variable tree, its
    # own warmed executables and its own lifecycle breaker, so one hung or
    # poisoned chip is one fault domain — its batch is requeued onto a
    # healthy replica instead of failing the service. 1 keeps the PR 7/11
    # single-engine path bit-identical (no fleet wrapper, uncommitted
    # default-device placement). Requires sharding_rules="dp": a replica IS
    # one device; spatial presets shard one engine over all devices, which
    # is the opposite trade (pick one per deployment).
    replicas: int = 1
    # Default budget for service.drain(): how long a graceful shutdown
    # waits for queued + in-flight requests before closing anyway.
    drain_timeout_s: float = 30.0
    # Persistent AOT executable cache (serving/aot.py; `serve
    # --aot_cache_dir`): serialized compiled executables keyed on (jaxlib
    # version, backend/topology, bucket table, model-config fingerprint).
    # On boot each warmup entry deserializes instead of tracing — a warm
    # cache boots with ZERO compiles. None disables (legacy trace-at-boot).
    aot_cache_dir: Optional[str] = None
    # HLO contract audit (tools/graftaudit; `serve --audit`): warm() snapshots
    # every executable it compiles (HLO text + carried-state shardings +
    # donation table) into engine.audit_records, and AOT cache entries carry
    # the snapshot so a cache-HIT boot replays it — the audit always covers
    # exactly the executables that were warmed. Off by default: snapshots
    # retain the (large) HLO text for the life of the engine.
    hlo_audit: bool = False
    # Automatic replica respawn (fleet only): when a replica breaker goes
    # sticky-`failed`, boot a fresh engine from the AOT cache onto that
    # device, validate it against the serving tree and enter it in breaker
    # probation (serving/fleet.replace_replica). Off by default: without it
    # a failed replica stays failed until operator action — the PR 11/12
    # semantics some deployments (and the fault-injection tests) rely on.
    auto_respawn: bool = False
    # --- observability (obs/ package; README "Observability") ---
    # Where diagnostics land: the flight recorder dumps
    # <log_dir>/flight_recorder.json on breaker trips, watchdog fires, and
    # service close. None disables dumps (tracing still runs in memory and
    # feeds /healthz counters).
    log_dir: Optional[str] = None
    # Flight-recorder ring capacity: the last N spans/events kept for the
    # dump (admission -> queue -> stage -> chunk -> finalize -> respond
    # taxonomy). 0 disables recording entirely.
    flight_recorder_events: int = 512

    def __post_init__(self):
        if self.sharding_rules not in SHARDING_PRESETS:
            raise ValueError(
                f"sharding_rules {self.sharding_rules!r} not in {SHARDING_PRESETS}"
            )
        if not self.buckets:
            raise ValueError("buckets must be non-empty")
        for hw in self.buckets:
            if len(hw) != 2 or hw[0] % self.divis_by or hw[1] % self.divis_by:
                raise ValueError(
                    f"bucket {hw} must be (H, W) with both multiples of "
                    f"divis_by ({self.divis_by})"
                )
        if len(set(self.buckets)) != len(self.buckets):
            raise ValueError(f"duplicate buckets in {self.buckets}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.chunk_iters < 1:
            raise ValueError(f"chunk_iters must be >= 1, got {self.chunk_iters}")
        if self.max_iters < 1:
            raise ValueError(f"max_iters must be >= 1, got {self.max_iters}")
        if self.deadline_ms < 0:
            raise ValueError(f"deadline_ms must be >= 0, got {self.deadline_ms}")
        if self.batch_window_ms < 0:
            raise ValueError(
                f"batch_window_ms must be >= 0, got {self.batch_window_ms}"
            )
        if self.max_streams < 1:
            raise ValueError(f"max_streams must be >= 1, got {self.max_streams}")
        if not 1 <= self.breaker_degrade_after <= self.breaker_fail_after:
            raise ValueError(
                f"need 1 <= breaker_degrade_after "
                f"({self.breaker_degrade_after}) <= breaker_fail_after "
                f"({self.breaker_fail_after})"
            )
        if self.breaker_probation < 1:
            raise ValueError(
                f"breaker_probation must be >= 1, got {self.breaker_probation}"
            )
        if self.hang_timeout_s < 0:
            raise ValueError(
                f"hang_timeout_s must be >= 0, got {self.hang_timeout_s}"
            )
        if self.drain_timeout_s < 0:
            raise ValueError(
                f"drain_timeout_s must be >= 0, got {self.drain_timeout_s}"
            )
        if self.replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {self.replicas}")
        if self.replicas > 1 and self.sharding_rules != "dp":
            raise ValueError(
                f"replicas={self.replicas} requires sharding_rules='dp': a "
                "fleet pins one whole engine per device, while "
                f"{self.sharding_rules!r} shards one engine across all "
                "devices — the two placements are mutually exclusive"
            )
        if self.auto_respawn and self.replicas < 2:
            raise ValueError(
                "auto_respawn requires replicas >= 2: respawn replaces one "
                "fleet replica while the others keep serving — a single "
                "engine has nothing to fail over to (restart it instead)"
            )
        if self.flight_recorder_events < 0:
            raise ValueError(
                "flight_recorder_events must be >= 0, "
                f"got {self.flight_recorder_events}"
            )
        if self.video is not None:
            if self.video.chunk_iters != self.chunk_iters:
                raise ValueError(
                    f"video.chunk_iters ({self.video.chunk_iters}) must match "
                    f"serving chunk_iters ({self.chunk_iters}): stream frames "
                    "run through the same warmed chunk executables"
                )
            if self.video.warm_iters > self.max_iters:
                raise ValueError(
                    f"video.warm_iters ({self.video.warm_iters}) must be <= "
                    f"max_iters ({self.max_iters})"
                )

    @property
    def batch_sizes(self) -> Tuple[int, ...]:
        """Warmed batch sizes: powers of two up to and including max_batch."""
        sizes = []
        b = 1
        while b < self.max_batch:
            sizes.append(b)
            b *= 2
        sizes.append(self.max_batch)
        return tuple(sizes)

    @property
    def num_chunks(self) -> int:
        """max_iters rounded up to whole chunks."""
        return -(-self.max_iters // self.chunk_iters)


@dataclasses.dataclass(frozen=True)
class FrontierConfig:
    """Front-tier router config (serving/frontier.py; ROADMAP item 4).

    The frontier is a stdlib HTTP process routing /predict across N
    backend `StereoService` hosts. It holds no model, no device and no
    carry state — only routing tables, per-backend breakers (the same
    `ServingLifecycle` machine the backends run) and counters — so a
    frontier restart loses nothing but stream pinnings (streams simply
    cold-start on their next frame).
    """

    # Backend addresses as "host:port" strings. Order is only a tiebreak:
    # routing prefers admissible backends with the fewest in-flight
    # requests.
    backends: Tuple[str, ...] = ()
    host: str = "127.0.0.1"
    port: int = 8081
    # Active health probing: every backend's /healthz is polled at this
    # interval; probe failures feed the same per-backend breaker as
    # forwarding failures, and probe successes are the ONLY thing that can
    # move a sticky-`failed` backend to probation (real traffic then earns
    # it back to healthy).
    health_interval_s: float = 2.0
    health_timeout_s: float = 5.0
    # Per-forward read timeout. Generous by default: a backend may be
    # queueing behind a large bucket; the deadline_ms inside the request
    # is the latency authority, this only bounds a wedged connection.
    request_timeout_s: float = 600.0
    # Retry policy for idempotent plain requests (streams never retry
    # blindly — they migrate, see frontier.py): attempts counts the total
    # tries, backoff is utils/retry.py's jittered exponential schedule.
    retry_attempts: int = 3
    retry_base_delay_s: float = 0.05
    retry_max_delay_s: float = 2.0
    retry_jitter: float = 0.5
    # Retry budget: retries are allowed while
    #   retries_total < retry_budget_min + retry_budget_percent% * requests
    # so a sick fleet can't melt itself with retry amplification, while a
    # cold frontier (zero requests yet) can still retry its first failure.
    retry_budget_percent: float = 20.0
    retry_budget_min: int = 10
    # Opt-in tail-latency hedging: after a plain request has been pending
    # for max(live queue-wait p95, hedge_floor_ms), dispatch a duplicate to
    # a DIFFERENT backend and take the first answer. Off by default —
    # hedging doubles work under exactly the load that makes tails long.
    hedge: bool = False
    hedge_floor_ms: float = 50.0
    # Overload brownout: when the worst backend queue-wait p95 crosses
    # brownout_queue_p95_ms (0 disables), the frontier tightens forwarded
    # requests — deadline_ms clamped to brownout_deadline_ms (if > 0) and
    # max_iters capped at brownout_max_iters (if > 0) — so the anytime
    # engines early-exit: quality degrades before ANY request is shed.
    # Hysteresis: brownout disengages only once the p95 falls below
    # threshold * brownout_recover_ratio.
    brownout_queue_p95_ms: float = 0.0
    brownout_deadline_ms: float = 0.0
    brownout_max_iters: int = 0
    brownout_recover_ratio: float = 0.5
    # Per-backend breaker thresholds (ServingLifecycle): forwarding/probe
    # failures degrade after N, fail after M; probation successes heal.
    breaker_degrade_after: int = 1
    breaker_fail_after: int = 3
    breaker_probation: int = 2
    # Graceful-shutdown budget: how long drain() waits for in-flight
    # forwards before closing anyway.
    drain_timeout_s: float = 30.0
    # Stream-session table ceiling (LRU eviction beyond it; an evicted
    # stream's next frame is routed fresh and cold-starts on its backend).
    max_sessions: int = 4096
    # Checkpoint rollout orchestration (POST /rollout, `frontier --rollout`):
    # the frontier rolls /reload across its backends one at a time —
    # quiesce, reload, verify (healthz generation advance + bit-wise canary
    # against the new-generation reference), probation — and aborts +
    # rolls already-swapped backends back on any failure.
    #
    # What happens to stream sessions pinned to the backend being swapped:
    #   "migrate" — the session moves to another backend immediately via
    #               the generation-aliased affinity path (guaranteed cold
    #               restart there);
    #   "hold"    — frames park until their host swaps back into rotation
    #               (carry survives; bounded by rollout_hold_timeout_s,
    #               after which the frame migrates anyway).
    rollout_stream_policy: str = "migrate"
    # Consecutive successful orchestrator probes (healthz on the NEW
    # generation) a swapped backend must pass before the roll proceeds.
    rollout_probation: int = 2
    # Per-backend budget for in-flight forwards to drain after quiesce.
    rollout_drain_timeout_s: float = 30.0
    # Budget for a swapped backend's /healthz to report the new generation.
    rollout_verify_timeout_s: float = 30.0
    # Ceiling on how long a request parks during the rollout flip window
    # (and a "hold"-policy stream frame waits for its host) before the
    # frontier gives up and sheds/migrates.
    rollout_hold_timeout_s: float = 60.0
    # Orchestrator probe cadence while verifying/probating one backend.
    rollout_probe_interval_s: float = 0.1
    # Flight recorder (obs/trace.py), same semantics as ServeConfig.
    log_dir: Optional[str] = None
    flight_recorder_events: int = 512

    def __post_init__(self):
        if not self.backends:
            raise ValueError("backends must be non-empty")
        if len(set(self.backends)) != len(self.backends):
            raise ValueError(f"duplicate backends in {self.backends}")
        for addr in self.backends:
            host, sep, port = str(addr).rpartition(":")
            if not sep or not host or not port.isdigit():
                raise ValueError(
                    f"backend {addr!r} must look like host:port"
                )
        if self.health_interval_s <= 0:
            raise ValueError(
                f"health_interval_s must be > 0, got {self.health_interval_s}"
            )
        if self.health_timeout_s <= 0:
            raise ValueError(
                f"health_timeout_s must be > 0, got {self.health_timeout_s}"
            )
        if self.request_timeout_s <= 0:
            raise ValueError(
                f"request_timeout_s must be > 0, got {self.request_timeout_s}"
            )
        if self.retry_attempts < 1:
            raise ValueError(
                f"retry_attempts must be >= 1, got {self.retry_attempts}"
            )
        if self.retry_base_delay_s < 0 or self.retry_max_delay_s < 0:
            raise ValueError("retry delays must be >= 0")
        if self.retry_budget_percent < 0:
            raise ValueError(
                f"retry_budget_percent must be >= 0, "
                f"got {self.retry_budget_percent}"
            )
        if self.retry_budget_min < 0:
            raise ValueError(
                f"retry_budget_min must be >= 0, got {self.retry_budget_min}"
            )
        if self.hedge_floor_ms < 0:
            raise ValueError(
                f"hedge_floor_ms must be >= 0, got {self.hedge_floor_ms}"
            )
        if self.brownout_queue_p95_ms < 0:
            raise ValueError(
                f"brownout_queue_p95_ms must be >= 0, "
                f"got {self.brownout_queue_p95_ms}"
            )
        if self.brownout_queue_p95_ms > 0 and not (
            self.brownout_deadline_ms > 0 or self.brownout_max_iters > 0
        ):
            raise ValueError(
                "brownout enabled (brownout_queue_p95_ms > 0) but no action "
                "knob set: need brownout_deadline_ms > 0 or "
                "brownout_max_iters > 0 — a brownout that tightens nothing "
                "is a no-op pretending to shed load"
            )
        if not 0 < self.brownout_recover_ratio <= 1:
            raise ValueError(
                f"brownout_recover_ratio must be in (0, 1], "
                f"got {self.brownout_recover_ratio}"
            )
        if not 1 <= self.breaker_degrade_after <= self.breaker_fail_after:
            raise ValueError(
                f"need 1 <= breaker_degrade_after "
                f"({self.breaker_degrade_after}) <= breaker_fail_after "
                f"({self.breaker_fail_after})"
            )
        if self.breaker_probation < 1:
            raise ValueError(
                f"breaker_probation must be >= 1, got {self.breaker_probation}"
            )
        if self.drain_timeout_s < 0:
            raise ValueError(
                f"drain_timeout_s must be >= 0, got {self.drain_timeout_s}"
            )
        if self.max_sessions < 1:
            raise ValueError(
                f"max_sessions must be >= 1, got {self.max_sessions}"
            )
        if self.rollout_stream_policy not in ("migrate", "hold"):
            raise ValueError(
                f"rollout_stream_policy must be 'migrate' or 'hold', "
                f"got {self.rollout_stream_policy!r}"
            )
        if self.rollout_probation < 1:
            raise ValueError(
                f"rollout_probation must be >= 1, got {self.rollout_probation}"
            )
        for knob in (
            "rollout_drain_timeout_s",
            "rollout_verify_timeout_s",
            "rollout_hold_timeout_s",
            "rollout_probe_interval_s",
        ):
            if getattr(self, knob) <= 0:
                raise ValueError(
                    f"{knob} must be > 0, got {getattr(self, knob)}"
                )
        if self.flight_recorder_events < 0:
            raise ValueError(
                "flight_recorder_events must be >= 0, "
                f"got {self.flight_recorder_events}"
            )
