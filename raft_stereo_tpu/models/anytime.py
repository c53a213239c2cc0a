"""Chunked "anytime" decomposition of the test-mode forward for serving.

RAFT-Stereo's iterative ConvGRU refinement emits a full disparity field at
EVERY iteration, which makes deadline-aware early exit a structural property
rather than a hack — but the monolithic `RAFTStereo.__call__` bakes the
iteration count into one compiled program, so a server that wants to check a
deadline mid-refinement would have to recompile per iteration count. This
module splits the forward at its two natural seams into three independently
jittable stages that carry `(hidden, flow)` state across host boundaries:

    AnytimePrelude   images -> refinement state        (encoders, corr state)
    AnytimeChunk     state  -> state, `chunk_iters` GRU iterations further
    AnytimeFinalize  state  -> (low_res_flow, flow_up) (mask head + upsample)

Composing prelude + k chunks + finalize computes EXACTLY the monolithic
`model.apply(variables, i1, i2, iters=k*chunk_iters, test_mode=True)` — the
same submodule names ("cnet", "fnet", "context_zqr_conv{i}", "iteration",
"mask_head") are constructed against the same variables tree, so one
checkpoint drives both paths and the serving e2e test asserts bit-identical
outputs. The host checks deadlines BETWEEN chunk calls with zero recompiles
(every stage is fixed-shape) and finalizes the best-so-far state when a
request's deadline hits.

The state is a plain dict pytree, so it device-round-trips through jit
without restructuring:

    {"net": (h3, h2, h1), "coords1": ..., "context": ..., "corr": ...,
     "coords0": ...}
"""

from __future__ import annotations

from typing import Optional

from flax import linen as nn
import jax
import jax.numpy as jnp

from raft_stereo_tpu.config import RAFTStereoConfig
from raft_stereo_tpu.models.raft_stereo import _IterationBody, encode_features
from raft_stereo_tpu.models.update import UpsampleMaskHead
from raft_stereo_tpu.utils.geometry import convex_upsample

Array = jax.Array


class AnytimePrelude(nn.Module):
    """Images -> refinement state: the loop-invariant forward prefix
    (encoders + corr build), shared verbatim with RAFTStereo.__call__
    through `encode_features`."""

    config: RAFTStereoConfig

    @nn.compact
    def __call__(self, image1: Array, image2: Array, flow_init: Optional[Array] = None):
        net, context, corr_state, coords0, coords1 = encode_features(
            self.config, image1, image2, test_mode=True
        )
        # Warm start (video streaming, video/session.py): seed coords1 with a
        # prior low-res flow — identical ops to the monolithic path
        # (raft_stereo.py flow_init handling), so chunked warm-started
        # refinement stays bit-identical to a direct flow_init apply. Under
        # one jit object the None and array cases are separate cache entries;
        # the serving engine warms both so streams never recompile.
        if flow_init is not None:
            flow_init = jnp.asarray(flow_init)
            if flow_init.ndim == 4:
                flow_init = flow_init[..., 0]
            coords1 = coords1 + flow_init
        return {
            "net": net,
            "coords1": coords1,
            "context": context,
            "corr": corr_state,
            "coords0": coords0,
        }


class AnytimeChunk(nn.Module):
    """Advance the refinement state by `chunk_iters` GRU iterations — the
    same scanned `_IterationBody` (name "iteration") as the monolithic
    forward, so k sequential chunk applications reproduce one
    `iters=k*chunk_iters` scan exactly (the scan body is iteration-
    independent; only the carry advances)."""

    config: RAFTStereoConfig
    chunk_iters: int

    @nn.compact
    def __call__(self, state):
        body = nn.scan(
            _IterationBody,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
            out_axes=0,
            length=self.chunk_iters,
        )(config=self.config, test_mode=True, name="iteration")
        (net, coords1), _ = body(
            (state["net"], state["coords1"]),
            state["context"],
            state["corr"],
            state["coords0"],
        )
        return dict(state, net=net, coords1=coords1)


class AnytimeFinalize(nn.Module):
    """State -> (low_res_flow, flow_up): the test-mode epilogue (mask head +
    convex upsample) on whatever refinement state exists — callable after
    ANY number of chunks, which is what makes the engine anytime."""

    config: RAFTStereoConfig

    @nn.compact
    def __call__(self, state):
        cfg = self.config
        flow_lowres = state["coords1"] - state["coords0"]
        mask = UpsampleMaskHead(cfg.n_downsample, name="mask_head")(
            state["net"][0]
        ).astype(jnp.float32)
        flow_up = convex_upsample(
            flow_lowres[..., None], mask, cfg.downsample_factor
        )
        return flow_lowres, flow_up
