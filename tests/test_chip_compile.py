"""Compile-only guard: the main path's Pallas kernels, at real widths, are
accepted by the TPU compiler for a described (not attached) v5e.

Interpret mode — what every other kernel test runs — cannot see what Mosaic
refuses: a relayout it has no rule for, a slice off the tiling, too much
VMEM. These compiles can, in about two seconds each and with no chip
(`on-chip-measurement` guide, section 2). Nothing runs, so they say nothing
about results or times; parity lives in the interpret-mode tests and the
on-chip check in `chip_smoke.py`.

One file and one process on purpose: describing the topology takes
`/tmp/libtpu_lockfile`, and two processes doing it at once abort.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from raft_stereo_tpu.ops import corr_pallas, encoder_pallas, gru_tail_pallas

# Middlebury-F after ÷32 padding is 1984x2880; the disparity field, the
# feature maps and the correlation rows live at 1/4 of it.
MF_H4, MF_W4 = 496, 720
# The SceneFlow recipe step: batch 4, 320x720 crops.
TR_B, TR_H4, TR_W4 = 4, 80, 180
LEVELS, RADIUS, FDIM = 4, 4, 256


@pytest.fixture(scope="module")
def v5e():
    """SingleDeviceSharding on one chip of a described v5e:2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no libtpu / lockfile held elsewhere
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc!r}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep these out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _tpu_backend(monkeypatch):
    """The kernels ask `jax.default_backend()` whether to interpret; the
    process is on the CPU, so the test answers for the described chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _padded_pyramid(rows, w1, w2, dtype, sharding):
    _, w1_pad = corr_pallas._w1_blocks(w1)
    out = []
    for level in range(LEVELS):
        w2p = corr_pallas._round_up(w2 >> level, 128)
        out.append(jax.ShapeDtypeStruct((rows, w1_pad, w2p), dtype, sharding=sharding))
    return tuple(out)


def _assert_kernel_compiled(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel was not lowered through Mosaic"
    return text


@pytest.mark.parametrize("corr_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("prefetch", [False, True], ids=["dense", "prefetch"])
def test_lookup_compiles_at_middlebury_f(v5e, corr_dtype, prefetch):
    padded = _padded_pyramid(MF_H4, MF_W4, MF_W4, corr_dtype, v5e)
    coords = jax.ShapeDtypeStruct((1, MF_H4, MF_W4), jnp.float32, sharding=v5e)
    lookup = (
        corr_pallas.prefetch_corr_lookup_padded
        if prefetch
        else corr_pallas._lookup_pallas_padded
    )
    _assert_kernel_compiled(
        lambda p, c: lookup(p, c, RADIUS, jnp.bfloat16), padded, coords
    )


@pytest.mark.parametrize("corr_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_train_lookup_and_scatter_compile_at_recipe_shape(v5e, corr_dtype):
    """Forward lookup + its scatter backward (the custom VJP), batch 4 at
    320x720 crops: the two kernels every training step runs."""
    padded = _padded_pyramid(TR_B * TR_H4, TR_W4, TR_W4, corr_dtype, v5e)
    coords = jax.ShapeDtypeStruct((TR_B, TR_H4, TR_W4), jnp.float32, sharding=v5e)

    def loss(p, c):
        taps = corr_pallas.pallas_corr_lookup_padded(p, c, RADIUS, jnp.bfloat16)
        return jnp.sum(taps.astype(jnp.float32))

    text = _assert_kernel_compiled(jax.value_and_grad(loss), padded, coords)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2  # lookup, scatter


def test_fused_gru_tail_and_motion_tail_compile_at_middlebury_f(v5e):
    gate = jax.ShapeDtypeStruct((1, MF_H4, MF_W4, 128), jnp.bfloat16, sharding=v5e)
    _assert_kernel_compiled(gru_tail_pallas.fused_gru_tail, gate, gate, gate, gate, gate)
    pre = jax.ShapeDtypeStruct((1, MF_H4, MF_W4, 126), jnp.bfloat16, sharding=v5e)
    flow = jax.ShapeDtypeStruct((1, MF_H4, MF_W4, 1), jnp.bfloat16, sharding=v5e)
    _assert_kernel_compiled(gru_tail_pallas.fused_motion_tail, pre, flow)


def test_fused_conv_s2d_compiles_at_middlebury_f(v5e):
    """The encoder's layer1 conv in the W-space-to-depth domain: the stem
    runs at full resolution, so the operand is (1, 1984, 1440, 128)."""
    x = jax.ShapeDtypeStruct((1, 4 * MF_H4, 2 * MF_W4, 128), jnp.bfloat16, sharding=v5e)
    w = jax.ShapeDtypeStruct((3, 3, 128, 128), jnp.bfloat16, sharding=v5e)
    bias = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=v5e)
    aff = jax.ShapeDtypeStruct((1, 2, 128), jnp.float32, sharding=v5e)
    _assert_kernel_compiled(
        lambda x, w, b, a: encoder_pallas.fused_conv_s2d(
            x, w, b, a, affine_form="in", emit_stats=True
        ),
        x, w, bias, aff,
    )


@pytest.mark.parametrize("corr_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_fused_pyramid_state_compiles_at_middlebury_f(v5e, corr_dtype):
    fmap = jax.ShapeDtypeStruct((1, MF_H4, MF_W4, FDIM), jnp.float32, sharding=v5e)
    _assert_kernel_compiled(
        lambda a, b: corr_pallas.fused_pyramid_state(a, b, LEVELS, corr_dtype=corr_dtype),
        fmap, fmap,
    )


@pytest.mark.parametrize("copy", ["dispatch", "combine_backward", "combine", "dispatch_backward"])
def test_live_row_copies_compile_at_the_token_cells_chunk(v5e, copy):
    """The expert layer's copies at a chunk of `sdar-a3b-train-blockdiff-4k`:
    4096 positions of 2048 bf16 (whole in VMEM as 32-bit words), a buffer of
    32,768 + 16 x 128 rows in tiles of 128, the rows' assignments and the
    assignments' weights in SMEM."""
    from raft_stereo_tpu.ops import grouped_matmul, tile_rows

    positions, width, tile = 4096, 2048, 128
    rows = grouped_matmul.rows_bound(positions * 8, 16, tile)
    assert tile_rows.fits(positions, width, jnp.bfloat16, rows, tile, 8)
    shape = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    table, buffer = shape((positions, width), jnp.bfloat16), shape((rows, width), jnp.bfloat16)
    source, num_tiles, weights = shape((rows,), jnp.int32), shape((1,), jnp.int32), shape((positions * 8,), jnp.float32)
    if copy == "dispatch":
        _assert_kernel_compiled(lambda t, s, n: tile_rows.gather_rows(t, s, n, tile, 8), table, source, num_tiles)
    elif copy == "combine_backward":
        _assert_kernel_compiled(
            lambda t, s, n, w, b: tile_rows.gather_rows(t, s, n, tile, 8, w, b), table, source, num_tiles, weights, buffer)
    elif copy == "combine":
        _assert_kernel_compiled(
            lambda b, s, n, w: tile_rows.scatter_add_rows(b, s, n, tile, positions, 8, w), buffer, source, num_tiles, weights)
    else:
        _assert_kernel_compiled(
            lambda b, s, n: tile_rows.scatter_add_rows(b, s, n, tile, positions, 8), buffer, source, num_tiles)


@pytest.mark.parametrize("call", ["forward", "forward_and_backward"])
def test_expert_activation_compiles_at_the_token_cells_chunk(v5e, call):
    """`swiglu_rows` and its VJP at a chunk of `sdar-a3b-train-blockdiff-4k`:
    the two products' (rows, 1536) and (rows, 768) bf16 buffers in tiles of
    128, `num_tiles` in SMEM."""
    from raft_stereo_tpu.ops import grouped_matmul

    rows, tile = grouped_matmul.rows_bound(4096 * 8, 16, 128), 128
    gate_up = jax.ShapeDtypeStruct((rows, 2 * 768), jnp.bfloat16, sharding=v5e)
    num_tiles = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=v5e)
    if call == "forward":
        _assert_kernel_compiled(lambda g, n: grouped_matmul.swiglu_rows(g, n, tile), gate_up, num_tiles)
        return
    value = lambda g, n: jnp.sum(grouped_matmul.swiglu_rows(g, n, tile).astype(jnp.float32))
    text = _assert_kernel_compiled(jax.value_and_grad(value), gate_up, num_tiles)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2  # the activation, its backward


@pytest.mark.parametrize("call", ["forward", "forward_and_backward"])
def test_state_space_scan_compiles_at_the_hybrid_cells_row(v5e, call):
    """`ssd_scan` at a row of `granite-h-micro-train-causal-8k`: 8192
    positions in chunks of 256, 64 heads of 64 (4 a grid step: whole lane
    tiles), a state of 128, bf16."""
    from raft_stereo_tpu.ops import ssd_scan

    shape = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    operands = (shape((1, 8192, 64, 64), jnp.bfloat16), shape((1, 8192, 64), jnp.float32), shape((64,), jnp.float32),
                shape((1, 8192, 128), jnp.bfloat16), shape((1, 8192, 128), jnp.bfloat16), shape((64,), jnp.float32))

    def value(*o):
        y, final = ssd_scan.ssd_scan(*o, chunk=256, heads=4)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(final)

    if call == "forward":
        text = _assert_kernel_compiled(value, *operands)
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
        return
    text = _assert_kernel_compiled(jax.value_and_grad(value, argnums=(0, 1, 2, 3, 4, 5)), *operands)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2  # ssd_chunk, ssd_chunk_bwd


def test_causal_attention_compiles_at_the_hybrid_cells_row(v5e):
    """The causal entry of the attention kernels at head 64: 32 query heads
    on 8 key-value heads, 8192 positions in tiles of 512."""
    from raft_stereo_tpu.ops import block_attention

    q = jax.ShapeDtypeStruct((1, 32, 8192, 64), jnp.bfloat16, sharding=v5e)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 64), jnp.bfloat16, sharding=v5e)
    value = lambda q, k, v: jnp.sum(block_attention.causal_attention(q, k, v, 0.015625, 512).astype(jnp.float32))
    text = _assert_kernel_compiled(jax.value_and_grad(value, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 3  # forward, dq, dk/dv


@pytest.mark.parametrize("heads", [32, 4], ids=["q", "k"])
def test_head_prologue_compiles_at_the_token_cells_batch(v5e, heads):
    """`qk_norm_rope` and its VJP at `sdar-a3b-train-blockdiff-4k`'s batch: 4
    rows of 8192 positions, 32 query or 4 key heads of 128 (one lane tile a
    head), bf16 in and out, float32 tables; and no float32 array of the
    operand's size between the two calls and the rest of the program."""
    from raft_stereo_tpu.ops import qk_norm_rope

    shape = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    x, weight, table = shape((4, 8192, heads * 128), jnp.bfloat16), shape((128,), jnp.float32), shape((8192, 128), jnp.float32)
    value = lambda x, w, cos, sin: jnp.sum(qk_norm_rope.qk_norm_rope(x, w, cos, sin, heads, 1e-6).astype(jnp.float32))
    text = _assert_kernel_compiled(jax.value_and_grad(value, argnums=(0, 1)), x, weight, table, table)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2  # the prologue, its backward
    assert f"f32[4,8192,{heads * 128}]" not in text and f"f32[4,8192,{heads},128]" not in text
    with pytest.raises(ValueError, match="qk_norm_rope: a head dimension of 64"):
        qk_norm_rope.qk_norm_rope(shape((4, 8192, heads * 64), jnp.bfloat16), shape((64,), jnp.float32),
                                  shape((8192, 64), jnp.float32), shape((8192, 64), jnp.float32), heads, 1e-6)


@pytest.mark.parametrize("kind,heads", [("window", 64), ("full", 48)], ids=["window-group8", "full-group6"])
def test_attention_compiles_at_the_laguna_cells_row(v5e, kind, heads):
    """Both attention kinds of `laguna-xs2-train-causal-16k` at head 128 on 8
    key-value heads, 16,384 positions in tiles of 512: the window entry (64
    query heads, a window of 512: two key tiles a query tile) under its own
    kernel names, the causal entry at a group of 6 (48 query heads)."""
    from raft_stereo_tpu.ops import block_attention

    q = jax.ShapeDtypeStruct((1, heads, 16384, 128), jnp.bfloat16, sharding=v5e)
    kv = jax.ShapeDtypeStruct((1, 8, 16384, 128), jnp.bfloat16, sharding=v5e)
    scale = 128 ** -0.5
    if kind == "window":
        attend = lambda q, k, v: block_attention.window_attention(q, k, v, 512, scale, 512)
    else:
        attend = lambda q, k, v: block_attention.causal_attention(q, k, v, scale, 512)
    value = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32))
    text = _assert_kernel_compiled(jax.value_and_grad(value, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 3  # forward, dq, dk/dv
    name = "window_attention" if kind == "window" else "block_attention"
    other = "block_attention" if kind == "window" else "window_attention"
    assert all(f"%{name}{suffix}" in text for suffix in (".", "_dq.", "_dkv.")) and f"%{other}" not in text


def test_partial_rotary_prologue_compiles_at_the_laguna_cells_full_layer(v5e):
    """`qk_norm_rope` and its VJP with a rotary over HALF the head (tables of
    64 for heads of 128, YaRN's), 48 query heads, one row of 16,384
    positions: the two rotations and the select lower, and no float32 array
    of the operand's size appears beside the two calls."""
    from raft_stereo_tpu.ops import qk_norm_rope

    shape = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    x, weight, table = shape((1, 16384, 48 * 128), jnp.bfloat16), shape((128,), jnp.float32), shape((16384, 64), jnp.float32)
    value = lambda x, w, cos, sin: jnp.sum(qk_norm_rope.qk_norm_rope(x, w, cos, sin, 48, 1e-6).astype(jnp.float32))
    text = _assert_kernel_compiled(jax.value_and_grad(value, argnums=(0, 1)), x, weight, table, table)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2  # the prologue, its backward
    assert "f32[1,16384,6144]" not in text and "f32[1,16384,48,128]" not in text
