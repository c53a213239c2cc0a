"""What the trainer takes from a model family: how to initialise and what a
batch holds (`family_of`), and the loss of a batch (`make_loss`). Everything else of `Trainer` (the
sharded step around the loss, feed, lagged metrics, hygiene, watchdog, save,
run report) is shared as it is.

`family_of(model_config, sample_shape)` picks the family by the type of
`TrainConfig.model`; `sample_shape` is the shape of ONE sample: (H, W, C) of
an image for the stereo family, (L,) tokens for the three token families.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from raft_stereo_tpu.config import GraniteHybridConfig, LagunaConfig, RAFTStereoConfig, SDARMoEConfig, TrainConfig

Batch = Dict[str, jax.Array]
# (params, batch_stats, batch) -> (loss, metrics)
LossFn = Callable[[Any, Any, Batch], Tuple[jax.Array, Dict[str, jax.Array]]]


class RaftStereoFamily:
    """RAFT-Stereo: two images in, a sequence of disparity fields out, the
    sequence loss against `flow` where `valid`."""

    def __init__(self, sample_shape: Tuple[int, ...]):
        self.sample_shape = tuple(sample_shape)  # (H, W, C)

    def init_variables(self, config: TrainConfig, rng: jax.Array):
        from raft_stereo_tpu.models import init_model_variables

        h, w, c = self.sample_shape
        # Per-config cached jitted init (models/init_cache.py): a fresh
        # jax.jit wrapper here would re-compile flax init for every Trainer
        # construction; eager init is worse still (hundreds of tiny per-op XLA
        # compiles — tests/conftest.py docstring).
        return init_model_variables(config.model, image_hw=(h, w), rng=rng, channels=c)

    def batch_shapes(self, batch_size: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        h, w, c = self.sample_shape
        b = batch_size
        shapes = {"image1": (b, h, w, c), "image2": (b, h, w, c), "flow": (b, h, w, 1), "valid": (b, h, w)}
        return {name: (shape, jnp.float32) for name, shape in shapes.items()}

    def audit_meta(self, config: TrainConfig) -> Dict[str, Any]:
        h, w, _ = self.sample_shape
        return {"corr_dtype": config.model.corr_dtype, "sample": [h, w]}


class SDARMoEFamily:
    """The routed-expert decoder under block diffusion: `tokens` (B, L)
    int32, `masked` (B, L) bool (which positions the noised copy hides) and
    `noise_t` (B, L / block_length) float32 (each block's noise level) in, the
    block-diffusion loss out (models/sdar_moe.py)."""

    def __init__(self, sample_shape: Tuple[int, ...], block_length: int):
        (self.seq_len,) = tuple(sample_shape)
        if self.seq_len % block_length:
            raise ValueError(f"a sequence of {self.seq_len} does not hold whole blocks of {block_length}")
        self.blocks = self.seq_len // block_length

    def init_variables(self, config: TrainConfig, rng: jax.Array):
        from raft_stereo_tpu.models.sdar_moe import init_sdar_variables

        return init_sdar_variables(config.model, rng, self.seq_len)

    def batch_shapes(self, batch_size: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        return {
            "tokens": ((batch_size, self.seq_len), jnp.int32),
            "masked": ((batch_size, self.seq_len), jnp.bool_),
            "noise_t": ((batch_size, self.blocks), jnp.float32),
        }

    def audit_meta(self, config: TrainConfig) -> Dict[str, Any]:
        return {"sample": [self.seq_len], "expert_parallel": config.model.expert_parallel}


class GraniteHybridFamily:
    """The Mamba-2 / attention hybrid on the plain causal loss: `tokens`
    (B, L) int32 in, the mean next-token cross-entropy out
    (models/granite_hybrid.py)."""

    def __init__(self, sample_shape: Tuple[int, ...]):
        (self.seq_len,) = tuple(sample_shape)

    def init_variables(self, config: TrainConfig, rng: jax.Array):
        from raft_stereo_tpu.models.granite_hybrid import init_granite_variables

        return init_granite_variables(config.model, rng, self.seq_len)

    def batch_shapes(self, batch_size: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        return {"tokens": ((batch_size, self.seq_len), jnp.int32)}

    def audit_meta(self, config: TrainConfig) -> Dict[str, Any]:
        return {"sample": [self.seq_len], "layer_types": list(config.model.layer_types)}


class LagunaFamily(GraniteHybridFamily):
    """The routed-expert decoder of mixed attention kinds on the plain causal
    loss: the hybrid's batch, `tokens` (B, L) int32 alone
    (models/laguna.py)."""

    def init_variables(self, config: TrainConfig, rng: jax.Array):
        from raft_stereo_tpu.models.laguna import init_laguna_variables

        return init_laguna_variables(config.model, rng, self.seq_len)

    def audit_meta(self, config: TrainConfig) -> Dict[str, Any]:
        model = config.model
        return {"sample": [self.seq_len], "layer_types": list(model.layer_types),
                "mlp_layer_types": list(model.mlp_layer_types), "expert_parallel": model.expert_parallel}


def _raft_stereo_loss(config: TrainConfig) -> LossFn:
    from raft_stereo_tpu.models import RAFTStereo
    from raft_stereo_tpu.train.loss import sequence_loss

    model = RAFTStereo(config.model)

    def loss(params, batch_stats, batch):
        flows = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["image1"],
            batch["image2"],
            iters=config.train_iters,
        )
        return sequence_loss(
            flows, batch["flow"], batch["valid"], config.loss_gamma, config.max_flow
        )

    return loss


def _sdar_moe_loss(config: TrainConfig) -> LossFn:
    from raft_stereo_tpu.models.sdar_moe import SDARDecoder

    model = SDARDecoder(config.model)

    def loss(params, batch_stats, batch):
        return model.apply(
            {"params": params}, batch["tokens"], batch["masked"], batch["noise_t"], method="loss"
        )

    return loss


def _granite_hybrid_loss(config: TrainConfig) -> LossFn:
    from raft_stereo_tpu.models.granite_hybrid import GraniteHybrid

    model = GraniteHybrid(config.model)

    def loss(params, batch_stats, batch):
        return model.apply({"params": params}, batch["tokens"], method="loss")

    return loss


def _laguna_loss(config: TrainConfig) -> LossFn:
    from raft_stereo_tpu.models.laguna import Laguna

    model = Laguna(config.model)

    def loss(params, batch_stats, batch):
        return model.apply({"params": params}, batch["tokens"], method="loss")

    return loss


def make_loss(config: TrainConfig) -> LossFn:
    """The loss of a batch for the family of `config.model`; needs no sample
    shape."""
    if isinstance(config.model, RAFTStereoConfig):
        return _raft_stereo_loss(config)
    if isinstance(config.model, SDARMoEConfig):
        return _sdar_moe_loss(config)
    if isinstance(config.model, GraniteHybridConfig):
        return _granite_hybrid_loss(config)
    if isinstance(config.model, LagunaConfig):
        return _laguna_loss(config)
    raise TypeError(f"no model family for a {type(config.model).__name__}")


def family_of(model_config, sample_shape: Tuple[int, ...]):
    if isinstance(model_config, RAFTStereoConfig):
        return RaftStereoFamily(sample_shape)
    if isinstance(model_config, SDARMoEConfig):
        return SDARMoEFamily(sample_shape, model_config.block_length)
    if isinstance(model_config, GraniteHybridConfig):
        return GraniteHybridFamily(sample_shape)
    if isinstance(model_config, LagunaConfig):
        return LagunaFamily(sample_shape)
    raise TypeError(f"no model family for a {type(model_config).__name__}")
