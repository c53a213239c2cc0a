"""The `sdar-moe` family on the CPU at a tiny size (hidden 64, 4 heads of 16
on 2 key-value heads, 8 experts of 32 with 2 a token, 2 layers, 32 tokens a
row): the decoder against the plain reference on seeded weights, the two
kernels against their dense forms, the live-row copies against the gathers
they replace, the chip's share against the uncut model,
the trainer's rules, scopes and entry point for the family, and the stereo
step left as it was.
"""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import walk_checks
from benchmark import sdar_reference
from raft_stereo_tpu.config import RAFTStereoConfig, SDARMoEConfig, TrainConfig
from raft_stereo_tpu.models import sdar_moe
from raft_stereo_tpu.ops import block_attention as ba
from raft_stereo_tpu.ops import grouped_matmul as gm
from raft_stereo_tpu.ops import qk_norm_rope as qk
from raft_stereo_tpu.ops import tile_rows as tr

SEQ = 32
PUBLISHED = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=1e6)
TILES = dict(mixed_precision=False, moe_chunk=64, moe_tile_rows=8, attention_tile=16, loss_chunk=32)


def _configs(block_length=4, expert_parallel=1, expert_shard=0, **tiles):
    """(the program's config, the configuration-file dict the reference
    reads) for one chip's share of the tiny model."""
    published = dict(PUBLISHED, num_experts=PUBLISHED["num_experts"] // expert_parallel)
    program = dict(expert_parallel=expert_parallel, expert_shard=expert_shard, block_length=block_length,
                   mask_token_id=95)
    config = SDARMoEConfig.from_hf_config(published, **program, **dict(TILES, **tiles))
    return config, dict(published, program=program)


def _batch(block_length, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, 95, (batch, SEQ)), jnp.int32)
    noise_t = jnp.asarray(rng.uniform(0.001, 1.0, (batch, SEQ // block_length)), jnp.float32)
    masked = jnp.asarray(rng.uniform(size=(batch, SEQ)) < np.repeat(np.asarray(noise_t), block_length, axis=1))
    return {"tokens": tokens, "masked": masked, "noise_t": noise_t}


def _close(got, want, tol=2e-5):
    scale = float(jnp.abs(want).max()) + 1e-12
    return float(jnp.abs(got - want).max()) / scale < tol


# -- the decoder against the reference -------------------------------------------------


@pytest.mark.parametrize("block_length", [4, 16])
@pytest.mark.parametrize("share", [(1, 0), (2, 1), (4, 2)], ids=["whole", "half-1", "quarter-2"])
def test_decoder_loss_gradients_and_logits_match_the_reference(block_length, share):
    config, file_config = _configs(block_length, *share)
    params = sdar_moe.init_sdar_variables(config, jax.random.PRNGKey(1), SEQ)["params"]
    assert jax.tree.map(lambda x: tuple(x.shape), params) == sdar_reference.param_shapes(file_config)
    batch = _batch(block_length)
    model = sdar_moe.SDARDecoder(config)
    loss = lambda p: model.apply({"params": p}, batch["tokens"], batch["masked"], batch["noise_t"], method="loss")

    # one program a side: the loss with its gradient, and the logits
    @jax.jit
    def program(p):
        return jax.value_and_grad(loss, has_aux=True)(p), model.apply({"params": p}, batch["tokens"], batch["masked"])

    @jax.jit
    def reference(p):
        graded = jax.value_and_grad(lambda p: sdar_reference.loss(file_config, p, batch), has_aux=True)(p)
        return graded, sdar_reference.forward(file_config, p, batch["tokens"], batch["masked"])

    ((got, aux), grads), (logits, _) = program(params)
    ((want, held), want_grads), (want_logits, _) = reference(params)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert float(aux["moe_held_rows"]) == float(held)  # no row dropped, none invented
    assert float(aux["attn_interior_pair_share"]) == 2 / 8  # two tiles a half: the two clean pasts of eight pairs
    assert all(jax.tree.leaves(jax.tree.map(_close, grads, want_grads)))
    assert _close(logits, want_logits)


def test_chunked_experts_and_loss_equal_the_unchunked():
    chunked, _ = _configs()
    whole, _ = _configs(moe_chunk=4096, loss_chunk=4096)
    params = sdar_moe.init_sdar_variables(chunked, jax.random.PRNGKey(2), SEQ)["params"]
    batch = _batch(4)
    values = []
    for config in (chunked, whole):
        model = sdar_moe.SDARDecoder(config)
        loss = lambda p: model.apply({"params": p}, batch["tokens"], batch["masked"], batch["noise_t"], method="loss")[0]
        values.append(jax.jit(jax.value_and_grad(loss))(params))
    assert abs(float(values[0][0]) - float(values[1][0])) < 1e-6
    assert all(jax.tree.leaves(jax.tree.map(_close, values[0][1], values[1][1])))


# -- the chip's share against the uncut model ------------------------------------------


@pytest.mark.parametrize("expert_parallel", [2, 4, 8])
def test_the_shards_partial_results_add_up_to_the_uncut_layers(expert_parallel):
    """Every shard routes over all 8 experts and computes its own experts'
    part; the parts sum to what the uncut reference's expert layer gives."""
    whole, whole_file = _configs()
    params = sdar_moe.init_sdar_variables(whole, jax.random.PRNGKey(3), SEQ)["params"]["layers"]
    first = jax.tree.map(lambda x: x[0], params)
    m = jax.random.normal(jax.random.PRNGKey(4), (2 * 2 * SEQ, 64))
    want, want_rows = sdar_reference._experts(
        lambda x: x, whole_file, sdar_reference._dims(whole_file), first["experts"], first["router"], m, None)
    held = 8 // expert_parallel

    @jax.jit
    def parts(m):
        out = []
        for shard in range(expert_parallel):
            config, _ = _configs(4, expert_parallel, shard)
            mine = jax.tree.map(lambda x: x[shard * held:(shard + 1) * held], first["experts"])
            chosen, weights = sdar_moe.Router(config).apply({"params": first["router"]}, m)
            out.append(sdar_moe.Experts(config).apply({"params": mine}, m, chosen, weights)[:2])
        return out

    found = parts(m)
    total, rows = sum(y for y, _ in found), sum(int(counts.sum()) for _, counts in found)
    assert rows == int(want_rows) == m.shape[0] * 2
    assert _close(total, want)


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_the_vocabulary_slices_logits_concatenate_to_the_uncut_heads(shards):
    h = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    w_head = jax.random.normal(jax.random.PRNGKey(6), (64, 96))
    rows = 96 // shards
    slices = []
    for shard in range(shards):
        config = SDARMoEConfig.from_hf_config(dict(PUBLISHED, vocab_size=rows), **TILES)
        mine = {"w_head": w_head[:, shard * rows:(shard + 1) * rows]}
        slices.append(sdar_moe.LMHead(config).apply({"params": mine}, h))
    assert _close(jnp.concatenate(slices, axis=-1), jnp.dot(h, w_head, precision="highest"))


# -- block_attention -------------------------------------------------------------------


@pytest.mark.parametrize("seq,block,tile,heads", [(32, 4, 16, (4, 2)), (32, 16, 16, (4, 2)), (32, 4, 32, (2, 2)),
                                                  (32, 4, 8, (8, 1))])
def test_block_attention_forward_and_backward_match_the_dense_form(seq, block, tile, heads):
    hq, hkv = heads
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (2, hq, 2 * seq, 16))
    k = jax.random.normal(keys[1], (2, hkv, 2 * seq, 16))
    v = jax.random.normal(keys[2], (2, hkv, 2 * seq, 16))
    w = jax.random.normal(keys[3], q.shape)
    kernel = lambda q, k, v: (ba.block_attention(q, k, v, seq, block, tile) * w).sum()
    dense = lambda q, k, v: (ba.block_attention_dense(q, k, v, seq, block) * w).sum()
    forward = jax.jit(lambda q, k, v: (ba.block_attention(q, k, v, seq, block, tile), ba.block_attention_dense(q, k, v, seq, block)))
    assert _close(*forward(q, k, v))
    got, want = jax.jit(jax.grad(kernel, (0, 1, 2)))(q, k, v), jax.jit(jax.grad(dense, (0, 1, 2)))(q, k, v)
    assert all(_close(a, b) for a, b in zip(got, want))


@pytest.mark.parametrize("seq,block", [(32, 4), (32, 16), (48, 8)])
def test_mask_codes_give_the_rule_and_the_reference_s_mask(seq, block):
    mask = np.asarray(ba.block_mask(seq, block))
    assert np.array_equal(mask, np.asarray(sdar_reference.block_mask(seq, block)))
    b = (np.arange(2 * seq) % seq) // block
    noised = np.arange(2 * seq) < seq
    for i in (0, block, seq - 1, seq, seq + block, 2 * seq - 1):
        want = np.where(noised[i], (noised & (b == b[i])) | (~noised & (b < b[i])), ~noised & (b <= b[i]))
        assert np.array_equal(mask[i], want), i
    assert mask.sum() == seq * block + seq * seq


def test_tiles_no_query_can_see_are_never_visited():
    """The grid's last axis walks only visible tiles: the live steps over all
    query tiles count the tiles the dense mask touches."""
    seq, block, tile = 64, 4, 16
    nh = seq // tile
    mask = np.asarray(ba.block_mask(seq, block)).reshape(2 * nh, tile, 2 * nh, tile).any(axis=(1, 3))
    for qt in range(2 * nh):
        steps = int(ba._fwd_steps(jnp.int32(qt), nh)[1])
        visited = {int(ba._fwd_key_tile(jnp.int32(qt), jnp.int32(s), nh)) for s in range(steps)}
        assert visited == set(np.flatnonzero(mask[qt])), qt
        assert int(ba._fwd_key_tile(jnp.int32(qt), jnp.int32(nh), nh)) in visited  # a dead step copies nothing new
    for kt in range(2 * nh):
        steps = int(ba._bwd_steps(jnp.int32(kt), nh)[1])
        visited = {int(ba._bwd_query_tile(jnp.int32(kt), jnp.int32(u), nh)) for u in range(steps)}
        assert visited == set(np.flatnonzero(mask[:, kt])), kt


@pytest.mark.parametrize("seq,block,tile", [(64, 4, 16), (64, 8, 32), (32, 4, 8), (32, 16, 16), (4096, 4, 512)],
                         ids=["four-tiles", "two-tiles", "eight-tiles", "a-block-a-tile", "the-cell"])
def test_the_block_walk_calls_interior_the_wholly_visible_pairs_and_no_other(seq, block, tile):
    """Against the dense mask, from the query side and from the key side;
    where a tile is one block its diagonal pairs are wholly visible too and
    stay with the masked body."""
    t, walk = ba._Mask(seq, block).walk(tile)
    nh = seq // t
    interior, visited = walk_checks.interior_pairs(np.asarray(ba.block_mask(seq, block)), walk, t, every=t > block)
    assert (interior, visited) == (nh * (nh - 1), nh * (nh + 2))
    assert ba.interior_pair_share(seq, block, tile=tile) == interior / visited


def test_the_cells_interior_pair_share_is_56_of_80():
    assert ba.interior_pair_share(4096, 4) == 56 / 80 == jax.jit(lambda: ba.interior_pair_share(4096, 4, 0, 512))()


@pytest.mark.parametrize("seq,block,tile,heads", [(32, 4, 16, (4, 2)), (32, 4, 8, (8, 1)), (64, 16, 16, (2, 2))])
def test_block_attention_without_the_unmasked_body_keeps_every_bit(monkeypatch, seq, block, tile, heads):
    walk_checks.never_interior_keeps_the_bits(
        monkeypatch, ba._BlockWalk, lambda q, k, v: ba.block_attention(q, k, v, seq, block, tile), heads, 2 * seq, 16)


# -- grouped_matmul --------------------------------------------------------------------

E, TILE, A = 4, 8, 64
ROUTINGS = {
    "mixed": lambda rng: rng.integers(0, E + 1, A),
    "all_held": lambda rng: rng.integers(0, E, A),
    "none_held": lambda rng: np.full(A, E),
    "one_takes_all": lambda rng: np.full(A, 2),
}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_group_layout_places_every_held_assignment_once(routing):
    expert = ROUTINGS[routing](np.random.default_rng(8))
    layout = jax.tree.map(np.asarray, gm.group_layout(jnp.asarray(expert, jnp.int32), E, TILE))
    tiles = int(layout["num_tiles"][0])
    assert layout["row_live"].sum() == layout["held"].sum() == (expert < E).sum()
    assert np.array_equal(layout["counts"], np.bincount(expert, minlength=E + 1)[:E])
    for a in np.flatnonzero(expert < E):
        row = layout["slot_row"][a]
        assert layout["row_live"][row] and layout["row_source"][row] == a
        assert layout["tile_expert"][row // TILE] == expert[a]
    live_tiles = layout["tile_expert"][:tiles]
    assert list(live_tiles) == sorted(live_tiles) and set(live_tiles) == set(range(E))  # every expert a tile
    assert not layout["row_live"][tiles * TILE:].any()
    assert layout["row_live"].shape[0] == gm.rows_bound(A, E, TILE)  # room for the worst case


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_grouped_matmul_forward_and_both_backward_products_match_the_dense_form(routing):
    expert = jnp.asarray(ROUTINGS[routing](np.random.default_rng(9)), jnp.int32)
    layout = gm.group_layout(expert, E, TILE)
    groups = (layout["tile_expert"], layout["num_tiles"], TILE)
    rows = gm.rows_bound(A, E, TILE)
    x = jax.random.normal(jax.random.PRNGKey(10), (A, 32))
    w = jax.random.normal(jax.random.PRNGKey(11), (E, 32, 48))
    g = jax.random.normal(jax.random.PRNGKey(12), (rows, 48))
    live_tile = jnp.repeat(jnp.arange(rows // TILE) < layout["num_tiles"][0], TILE)[:, None]

    def through(product):
        def value(x, w):
            lhs = jnp.where(layout["row_live"][:, None], x[layout["row_source"]], 0.0)
            return (jnp.where(live_tile, product(lhs, w, *groups), 0.0) * g).sum()
        return jax.value_and_grad(value, (0, 1))(x, w)

    (got, got_grads), (want, want_grads) = through(gm.grouped_matmul), through(gm.grouped_matmul_dense)
    assert abs(float(got) - float(want)) < 1e-3
    assert all(_close(a, b) for a, b in zip(got_grads, want_grads))


# -- the copies of the live rows ------------------------------------------------------

COPY_E, COPY_K, COPY_C = 16, 3, 48  # 144 assignments in tiles of 8: a buffer of 34 tiles (and no power of two a position)


def _copy_routing(name, rng):
    """(C, k) expert ids over the 16 held experts, 16 = held elsewhere."""
    far = np.full((COPY_C, COPY_K), COPY_E)
    if name == "most_tiles_dead":  # an eighth of the router's experts is held here
        chosen = rng.integers(0, 8 * COPY_E, (COPY_C, COPY_K))
        return np.where(chosen < COPY_E, chosen, COPY_E)
    if name == "every_assignment_held":  # expert_parallel 1
        return rng.integers(0, COPY_E, (COPY_C, COPY_K))
    if name == "no_assignment_held":  # sixteen all-padding tiles
        return far
    if name == "two_held_experts_on_one_position":
        far[::3] = (3, 11, COPY_E)
        return far
    raise KeyError(name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", ["most_tiles_dead", "every_assignment_held", "no_assignment_held",
                                     "two_held_experts_on_one_position"])
def test_live_row_copies_match_the_gathers_they_replace_on_a_poisoned_buffer(routing, dtype):
    """`gather_rows` and `scatter_add_rows` against `_dispatch` / `_combine`
    and their backward functions, every dead tile of every buffer a kernel
    reads holding NaN: none of it may reach a row of a live tile, `y`, a
    gradient or `d_weights`."""
    expert = jnp.asarray(_copy_routing(routing, np.random.default_rng(13)), jnp.int32)
    layout = gm.group_layout(expert.reshape(-1), COPY_E, TILE)
    row_live, row_source, num_tiles = layout["row_live"], layout["row_source"], layout["num_tiles"]
    slot_row, held = layout["slot_row"].reshape(-1, COPY_K), layout["held"].reshape(-1, COPY_K)
    row_token, row_slot = row_source // COPY_K, row_source % COPY_K
    source = jnp.where(row_live, row_source, -1)
    rows = row_live.shape[0]
    live_tile = (jnp.arange(rows) < num_tiles[0] * TILE)[:, None]
    assert (routing == "no_assignment_held") == (int(num_tiles[0]) == COPY_E and not bool(row_live.any()))
    keys = jax.random.split(jax.random.PRNGKey(14), 4)
    m = jax.random.normal(keys[0], (COPY_C, 64)).astype(dtype)
    d_y = jax.random.normal(keys[1], (COPY_C, 64)).astype(dtype)
    weights = jax.random.uniform(keys[2], (COPY_C, COPY_K), jnp.float32)
    buffer = jnp.where(live_tile, jnp.where(row_live[:, None], jax.random.normal(keys[3], (rows, 64)), 0.0), jnp.nan)
    buffer = buffer.astype(dtype)
    clean = jnp.where(live_tile, buffer, 0)
    same = lambda got, want: np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    near = lambda got, want: np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=1e-2 if dtype == "bfloat16" else 1e-5, atol=1e-5)
    geometry = (source, num_tiles, TILE)

    # dispatch: the copy itself, padding rows of live tiles exact zeros
    got = tr.gather_rows(m, *geometry, COPY_K)
    same(jnp.where(live_tile, got, 0), sdar_moe._dispatch(m, row_token, row_live, slot_row, held))
    same(jnp.where(live_tile, got, 0), tr.gather_rows_dense(m, *geometry, COPY_K))
    # combine, and the dispatch's backward (a plain sum)
    y = tr.scatter_add_rows(buffer, *geometry, COPY_C, COPY_K, weights.reshape(-1))
    near(y, sdar_moe._combine(clean, weights, row_token, row_slot, row_live, slot_row, held))
    d_m = tr.scatter_add_rows(buffer, *geometry, COPY_C, COPY_K)
    near(d_m, sdar_moe._dispatch_bwd((slot_row, held), clean)[0])
    near(d_m, tr.scatter_add_rows_dense(buffer, *geometry, COPY_C, COPY_K))
    # the combine's backward: rows scaled in float32, the weights' gradient by rows
    want_rows, want_weights = sdar_moe._combine_bwd((clean, weights, row_token, row_slot, row_live, slot_row, held), d_y)[:2]
    d_rows, dots = tr.gather_rows(d_y, *geometry, COPY_K, weights=weights.reshape(-1), dot_with=buffer)
    same(jnp.where(live_tile, d_rows, 0), want_rows)
    d_weights = jnp.where(held, dots[slot_row], 0.0)
    near(d_weights, want_weights)
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in (y, d_m, d_weights))


def _swiglu(gate_up):
    """`silu(gate) * up` as the expert layer wrote it in `jax.numpy` before
    the kernel: widened to float32 over every row, rounded once."""
    f = gate_up.shape[1] // 2
    wide = gate_up.astype(jnp.float32)
    return (jax.nn.silu(wide[:, :f]) * wide[:, f:]).astype(gate_up.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", ["most_tiles_dead", "every_assignment_held", "no_assignment_held"])
def test_swiglu_rows_and_its_backward_match_the_jax_numpy_form_on_a_poisoned_buffer(routing, dtype):
    """The activation between the two products and its VJP, every dead tile
    of both buffers holding NaN: a live tile's rows are the `jax.numpy` form's
    to a unit in the last place of the dtype, and what a dead tile's rows
    hold reaches no row of the result (nor is anything written there: the
    result's dead rows are the same whatever poison the operands held)."""
    expert = jnp.asarray(_copy_routing(routing, np.random.default_rng(18)), jnp.int32)
    layout = gm.group_layout(expert.reshape(-1), COPY_E, TILE)
    num_tiles, rows, f = layout["num_tiles"], layout["row_live"].shape[0], 32
    live_tile = (jnp.arange(rows) < num_tiles[0] * TILE)[:, None]
    assert 0 < int(num_tiles[0]) < rows // TILE
    keys = jax.random.split(jax.random.PRNGKey(19), 2)
    values = (2.0 * jax.random.normal(keys[0], (rows, 2 * f)), jax.random.normal(keys[1], (rows, f)))
    poisoned = lambda poison: [jnp.where(live_tile, x, poison).astype(dtype) for x in values]
    ulp = float(jnp.finfo(dtype).eps)

    def near(got, want):
        got, want = (np.asarray(jnp.where(live_tile, x, 0), np.float32) for x in (got, want))
        assert np.isfinite(got).all()
        np.testing.assert_array_less(np.abs(got - want), ulp * np.abs(want) + 1e-6)

    results = []
    for poison in (jnp.nan, 1.0):
        gate_up, d_hidden = poisoned(poison)
        hidden, backward = jax.vjp(lambda x: gm.swiglu_rows(x, num_tiles, TILE), gate_up)
        (d_gate_up,) = backward(d_hidden)
        clean = jnp.where(live_tile, gate_up, 0)
        near(hidden, _swiglu(clean))
        near(d_gate_up, jax.vjp(_swiglu, clean)[1](jnp.where(live_tile, d_hidden, 0))[0])
        assert hidden.dtype == d_gate_up.dtype == jnp.dtype(dtype)
        results.append((hidden, d_gate_up))
    for under_nan, under_one in zip(*results):
        np.testing.assert_array_equal(np.asarray(under_nan, np.float32), np.asarray(under_one, np.float32))


def _expert_layer_values(config, devices):
    """value, (counts, live share) and the gradients to `m`, the experts'
    weights and the router's `weights`, from functions traced anew."""
    from raft_stereo_tpu.parallel.mesh import make_mesh
    from raft_stereo_tpu.parallel.sharding import ShardingEngine

    keys = jax.random.split(jax.random.PRNGKey(15), 4)
    m = jax.random.normal(keys[0], (4 * SEQ, 64))
    probs, chosen = jax.lax.top_k(jax.nn.softmax(jax.random.normal(keys[1], (4 * SEQ, 8))), 2)
    g = jax.random.normal(keys[2], m.shape)
    experts = sdar_moe.Experts(config)
    params = jax.jit(experts.init)(keys[3], m, chosen, probs)["params"]

    def value(params, m, weights):
        y, counts, live = experts.apply({"params": params}, m, chosen.astype(jnp.int32), weights)
        return (y * g).sum(), (y, counts, live)

    step = ShardingEngine(make_mesh((devices, 1)), "dp").wrap(jax.jit(jax.value_and_grad(value, (0, 1, 2), has_aux=True)))
    return step(params, m, probs / probs.sum(-1, keepdims=True))


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("moe_chunk", [32, 4096], ids=["chunked", "unchunked"])
def test_experts_with_live_copies_equal_the_gathers_over_every_row(monkeypatch, moe_chunk, devices):
    config, _ = _configs(4, 2, 1, moe_chunk=moe_chunk)
    assert tr.fits(32, 64, jnp.float32, gm.rows_bound(64, 4, 8), 8, 2)  # the kernels are what runs here
    (got, (y, counts, live)), grads = _expert_layer_values(config, devices)
    asked = []
    monkeypatch.setattr(sdar_moe, "fits", lambda *shapes: bool(asked.append(shapes)))
    (want, (want_y, want_counts, want_live)), want_grads = _expert_layer_values(config, devices)
    assert asked  # the second trace took the other path
    assert _close(y, want_y) and abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    assert np.array_equal(counts, want_counts) and float(live) == float(want_live)
    assert all(jax.tree.leaves(jax.tree.map(_close, grads, want_grads)))


def test_live_row_share_counts_the_live_tiles_of_a_hand_made_routing():
    """Shard 1 of 2 holds experts 4-7. Every position's first choice is
    expert 4, its second is held elsewhere: 64 rows = 8 tiles for expert 4
    and the one tile each of the other three, of a buffer of 128 + 4 x 8
    rows = 20 tiles; in chunks of 32 positions, 4 + 3 of 12 tiles twice."""
    m = jax.random.normal(jax.random.PRNGKey(16), (2 * SEQ, 64))
    chosen = jnp.tile(jnp.asarray([[4, 0]], jnp.int32), (2 * SEQ, 1))
    weights = jnp.full((2 * SEQ, 2), 0.5)
    for moe_chunk, want in ((4096, 11 / 20), (32, 7 / 12)):
        config, _ = _configs(4, 2, 1, moe_chunk=moe_chunk)
        experts = sdar_moe.Experts(config)
        params = jax.jit(experts.init)(jax.random.PRNGKey(17), m, chosen, weights)["params"]
        _, counts, live = jax.jit(experts.apply)({"params": params}, m, chosen, weights)
        assert list(np.asarray(counts)) == [2 * SEQ, 0, 0, 0] and abs(float(live) - want) < 1e-6
    layout = gm.group_layout(jnp.asarray([0, 4] * (2 * SEQ), jnp.int32), 4, 8)
    assert int(layout["num_tiles"][0]) * 8 / layout["row_live"].shape[0] == 11 / 20


# -- the head prologue: q/k norm, rotary and the heads-first layout in one pass -----------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tile", [16, 64], ids=["four-tiles", "one-tile"])
@pytest.mark.parametrize("heads", [32, 4, 1])
def test_qk_norm_rope_and_its_gradients_match_the_jax_numpy_form(heads, tile, dtype):
    """The forward, and the gradients by x and by the learned weight (the
    tables carry none), against `qk_norm_rope_dense`: float32 to 1e-5 of the
    largest value, bfloat16 to its step (2^-8 of the largest)."""
    batch, positions, d = 2, 64, 128
    dtype = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(heads), 3)
    x = (2.0 * jax.random.normal(keys[0], (batch, positions, heads * d))).astype(dtype)
    weight = jax.random.uniform(keys[1], (d,), minval=0.5, maxval=6.0)  # learned: not all ones
    d_out = jax.random.normal(keys[2], (batch, heads, positions, d)).astype(dtype)
    cos, sin = sdar_moe.rotary_tables(positions // 2, d, 1e6)
    kernel = lambda x, w, cos, sin: qk.qk_norm_rope(x, w, cos, sin, heads, 1e-6, tile)
    dense = lambda x, w: qk.qk_norm_rope_dense(x, w, cos, sin, heads, 1e-6)
    (got, pull), (want, pull_dense) = jax.vjp(kernel, x, weight, cos, sin), jax.vjp(dense, x, weight)
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -8
    assert got.shape == (batch, heads, positions, d) and got.dtype == dtype
    assert _close(got.astype(jnp.float32), want.astype(jnp.float32), tol)
    (d_x, d_weight, d_cos, d_sin), (want_x, want_weight) = pull(d_out), pull_dense(d_out)
    assert d_x.dtype == dtype and d_weight.dtype == jnp.float32
    assert _close(d_x.astype(jnp.float32), want_x.astype(jnp.float32), tol)
    # float32 sums of one product's roundings on either side: no worse than a step of the input
    assert _close(d_weight, want_weight, tol)
    assert not d_cos.any() and not d_sin.any()  # position ids are data


def test_qk_norm_rope_refuses_a_head_dimension_of_64_by_name(monkeypatch):
    """Compiled for the chip, a head is a column block of whole lane tiles;
    the refusal is a `ValueError` from shapes, before any kernel is built.
    (The interpreter has no lane tiles: the tiny models' heads of 16 run.)"""
    x, weight = jnp.ones((1, 16, 4 * 64)), jnp.ones((64,))
    cos, sin = sdar_moe.rotary_tables(8, 64, 1e6)
    assert qk.qk_norm_rope(x, weight, cos, sin, 4, 1e-6).shape == (1, 4, 16, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # as the chip has it
    with pytest.raises(ValueError, match="qk_norm_rope: a head dimension of 64 is not a multiple of 128"):
        qk.qk_norm_rope(x, weight, cos, sin, 4, 1e-6)
    with pytest.raises(ValueError, match="qk_norm_rope: x"):  # 3 heads of 64 are not x's 256 columns
        qk.qk_norm_rope(x, weight, cos, sin, 3, 1e-6)


@pytest.mark.parametrize("event,kernel", [
    ("%qk_norm_rope.3 = bf16[4,32,8192,128]{3,2,1,0} custom-call(bf16[4,8192,4096]{2,1,0} %a, f32[1,128]{1,0} %w)", "qk_norm_rope"),
    ("%qk_norm_rope_bwd.7 = (bf16[4,8192,512]{2,1,0}, f32[64,1,128]{2,1,0}) custom-call(bf16[4,4,8192,128]{3,2,1,0} %dz)", "qk_norm_rope_bwd"),
    ("%block_attention.11 = (bf16[4,32,8192,128]{3,2,1,0}, f32[4,32,1,8192]{3,2,1,0}) custom-call(s32[8192,1]{1,0} %c)", "block_attention"),
])
def test_a_trace_event_of_a_prologue_call_is_read_as_that_kernels_alone(event, kernel):
    """The roofline readers find a kernel's events by the exact name of its
    Pallas call: the two new calls are no attention kernel's, nor each
    other's."""
    from benchmark.readers import trace_kernel

    assert trace_kernel.kernel_of(event) == kernel


# -- the trainer's rules for the family -----------------------------------------------


def _tiny_train_config(tmp_path, model=None, **kwargs):
    return TrainConfig(model=model or _configs(4, 2, 1)[0], batch_size=2, num_steps=2, checkpoint_every=100,
                       handle_signals=False, checkpoint_dir=str(tmp_path / "ckpt"), log_dir=str(tmp_path / "logs"),
                       **kwargs)


def test_token_batch_and_state_rules():
    from jax.sharding import PartitionSpec as P

    from raft_stereo_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from raft_stereo_tpu.parallel.sharding import ShardingEngine
    from raft_stereo_tpu.train.families import family_of

    mesh = make_mesh((4, 1))
    config, file_config = _configs()
    template = {name: len(shape) for name, (shape, _) in family_of(config, (SEQ,)).batch_shapes(4).items()}
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32), sdar_reference.param_shapes(file_config),
                          is_leaf=lambda s: isinstance(s, tuple))
    dp, fsdp = ShardingEngine(mesh, "dp"), ShardingEngine(mesh, "fsdp")
    assert all(s.spec == P(DATA_AXIS, None) for s in dp.batch_shardings(template).values())
    assert dp.input_sharding(2, "tokens").spec == P(DATA_AXIS, None)
    assert dp.input_sharding(4).spec[0] == DATA_AXIS  # image-like still by rank
    assert set(jax.tree.leaves(dp.state_specs(shapes), is_leaf=lambda s: isinstance(s, P))) == {P()}
    specs = fsdp.state_specs(shapes)
    assert specs["layers"]["experts"]["w_gate"] == P(None, None, None, DATA_AXIS)
    assert specs["layers"]["attention"]["w_q"] == specs["layers"]["router"]["w_router"] == P(None, None, DATA_AXIS)
    assert specs["embed"]["embedding"] == specs["lm_head"]["w_head"] == P(None, DATA_AXIS)
    assert specs["norm"]["weight"] == specs["layers"]["attention"]["q_norm"]["weight"] == P()
    assert "tokens" in dp.explain(batch_template=template)


_STEP_METRICS = ("live_loss", "grad_norm", "moe_held_rows", "moe_live_row_share")


@pytest.fixture(scope="module")
def one_device_step(tmp_path_factory):
    """The tiny family's one-device step, built and compiled once a module
    (ahead of time, so the text read is the program that ran): (its
    optimized text, its metrics on `_batch(4)`)."""
    from raft_stereo_tpu.obs import scopes
    from raft_stereo_tpu.train.trainer import Trainer

    trainer = Trainer(_tiny_train_config(tmp_path_factory.mktemp("one_device"), seed=3), sample_shape=(SEQ,))
    step = trainer.train_step.lower(scopes.abstract(trainer.state), trainer._abstract_batch()).compile()
    _, metrics = step(trainer.state, trainer.sharding.place_batch(jax.tree.map(np.asarray, _batch(4))))
    return step.as_text(), tuple(float(metrics[k]) for k in _STEP_METRICS)


@pytest.mark.parametrize("preset", ["dp", "fsdp"])
def test_two_device_step_gives_the_one_device_steps_loss(tmp_path, one_device_step, preset):
    from raft_stereo_tpu.train.trainer import Trainer

    trainer = Trainer(_tiny_train_config(tmp_path, mesh_shape=(2, 1), sharding_rules=preset, seed=3), sample_shape=(SEQ,))
    _, metrics = trainer.train_step(trainer.state, trainer.sharding.place_batch(jax.tree.map(np.asarray, _batch(4))))
    losses = [one_device_step[1], tuple(float(metrics[k]) for k in _STEP_METRICS)]
    assert losses[0][2] == losses[1][2] and 0.0 < losses[0][3] == losses[1][3] <= 1.0
    assert abs(losses[0][0] - losses[1][0]) < 1e-5 and abs(losses[0][1] - losses[1][1]) < 1e-4


def test_token_steps_instructions_are_placed(one_device_step):
    """A token step's lowered instructions land in the family's rows of the
    ONE table, in every phase the step has; what the model wrote and no row
    takes is the layer scan's own plumbing. (Interpreted kernels print their
    bodies as nameless calls here; on the chip a kernel is one named custom
    call: PERF.md section 5 has the chip's shares.)"""
    from raft_stereo_tpu.obs import scopes

    text = one_device_step[0]
    seen = {}
    for op_name, opcode in scopes.instruction_scopes(text).values():
        component, phase = scopes.component(op_name, opcode)
        seen.setdefault(component, set()).add(phase)
        if component == "other" and "/layers/" in op_name:
            assert op_name.endswith(("/layers/add", "closed_call")) or "/layers/" not in op_name.split("while/body")[-1], op_name
    assert {"embed", "attention", "router", "experts", "lm_head", "loss", "optimizer"} <= set(seen)
    assert {"forward", "backward", "recompute"} <= seen["attention"] and "recompute" in seen["experts"]
    assert not set(seen) & {"encoder", "lookup", "gru08"}  # no stereo row takes a token step's instruction


def test_the_token_step_holds_no_float32_array_of_a_chunks_row_buffer(tmp_path):
    """With bfloat16 compute, what lies between the two expert products stays
    bfloat16 wherever it has a chunk's `rows_bound` rows: a float32 array of
    (rows, 2f) or (rows, f) in the step's lowered text is an elementwise pass
    over the worst case come back (on the chip: 214 MB a chunk through HBM,
    seven eighths of it rows that hold nothing). The interpreted kernels'
    bodies are in this text too, and widen one tile's block only."""
    from raft_stereo_tpu.obs import scopes
    from raft_stereo_tpu.train.trainer import Trainer

    f = 48  # (rows, 96) and (rows, 48) are no other array's shape in the step
    program = dict(expert_parallel=2, expert_shard=1, block_length=4, mask_token_id=95)
    model = SDARMoEConfig.from_hf_config(
        dict(PUBLISHED, num_experts=4, moe_intermediate_size=f), **program, **dict(TILES, mixed_precision=True))
    trainer = Trainer(_tiny_train_config(tmp_path, model), sample_shape=(SEQ,))
    text = trainer.train_step.lower(scopes.abstract(trainer.state), trainer._abstract_batch()).as_text()
    rows = gm.rows_bound(model.moe_chunk * model.num_experts_per_tok, model.num_experts, model.moe_tile_rows)
    assert f"tensor<{rows}x{2 * f}xbf16>" in text and f"tensor<{rows}x{f}xbf16>" in text  # the products' results
    assert f"tensor<{rows}x{2 * f}xf32>" not in text and f"tensor<{rows}x{f}xf32>" not in text


def test_the_token_step_holds_no_float32_array_of_a_q_sized_shape(tmp_path):
    """With bfloat16 compute, q stays bfloat16 from its projection to the
    attention kernels: a float32 array of (B, 2L, Hq, d), of half heads
    (B, 2L, Hq, d/2) or (B, Hq, 2L, d/2) in the step's lowered text, or an elementwise
    pass over (B, 2L, Hq x d) in float32, is the norm's or the rotary's pass
    through HBM come back (on the chip: 537 MB a pass, a half head padded to
    a whole lane tile). The interpreted kernels' bodies are in this text too,
    and widen one tile's (rows, d) block only."""
    from raft_stereo_tpu.obs import scopes
    from raft_stereo_tpu.train.trainer import Trainer

    heads, d = 6, 16  # 6 x 16 = 96 columns, three layers: no other array of the step is (2, 2L, 96)
    program = dict(expert_parallel=2, expert_shard=1, block_length=4, mask_token_id=95)
    model = SDARMoEConfig.from_hf_config(
        dict(PUBLISHED, num_experts=4, num_attention_heads=heads, num_hidden_layers=3), **program,
        **dict(TILES, mixed_precision=True))
    trainer = Trainer(_tiny_train_config(tmp_path, model), sample_shape=(SEQ,))
    text = trainer.train_step.lower(scopes.abstract(trainer.state), trainer._abstract_batch()).as_text()
    b, s = 2, 2 * SEQ
    assert f"tensor<{b}x{s}x{heads * d}xbf16>" in text and f"tensor<{b}x{heads}x{s}x{d}xbf16>" in text  # q as projected, q as attended
    # (the heads-first whole head, (B, Hq, 2L, d), is read in float32 by the attention backward's
    # `delta`, one fused reduction of do * o: not this chain's)
    for shape in ((b, s, heads, d), (b, s, heads, d // 2), (b, heads, s, d // 2)):
        assert f"tensor<{'x'.join(map(str, shape))}xf32>" not in text, shape
    # (B, 2L, Hq x d) in float32 is a projection's accumulator and nothing else: a product (forward or
    # transposed) and the rounding beside it, which the chip's compiler fuses into the product
    flat = [line for line in text.splitlines() if f"tensor<{b}x{s}x{heads * d}xf32>" in line]
    assert flat and all(" = stablehlo.dot_general " in line or " = stablehlo.convert " in line for line in flat)


@pytest.mark.parametrize("path,want", [
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/embed/take", ("embed", "forward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/while/body/closed_call/layers/input_norm/mul", ("attention", "forward")),
    ("jit(step_fn)/transpose(jvp(SDARDecoder.loss))/SDARDecoder.hidden/while/body/closed_call/checkpoint/layers/attention/block_attention/pallas_call", ("attention", "backward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/while/body/closed_call/layers/attention/qk_norm_rope/qk_norm_rope/pallas_call", ("attention", "forward")),
    ("jit(step_fn)/transpose(jvp(SDARDecoder.loss))/SDARDecoder.hidden/while/body/closed_call/checkpoint/rematted_computation/layers/attention/qk_norm_rope/qk_norm_rope/pallas_call", ("attention", "recompute")),
    ("jit(step_fn)/transpose(jvp(SDARDecoder.loss))/SDARDecoder.hidden/while/body/closed_call/checkpoint/layers/attention/qk_norm_rope/qk_norm_rope_bwd/pallas_call", ("attention", "backward")),
    ("jit(step_fn)/transpose(jvp(SDARDecoder.loss))/SDARDecoder.hidden/while/body/closed_call/checkpoint/layers/attention/qk_norm_rope/reduce_sum", ("attention", "backward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/while/body/closed_call/layers/post_attention_norm/rsqrt", ("router", "forward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/while/body/closed_call/layers/router/top_k", ("router", "forward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/while/body/closed_call/layers/experts/while/body/closed_call/checkpoint/grouped_matmul/pallas_call", ("experts", "forward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/while/body/closed_call/layers/experts/closed_call/while/body/closed_call/checkpoint/scatter_add_rows/scatter_add_rows/pallas_call", ("experts", "forward")),
    ("jit(step_fn)/transpose(jvp(SDARDecoder.loss))/SDARDecoder.hidden/while/body/closed_call/checkpoint/layers/experts/while/body/closed_call/checkpoint/rematted_computation/gather_rows/gather_rows/pallas_call", ("experts", "recompute")),
    ("jit(step_fn)/transpose(jvp(SDARDecoder.loss))/SDARDecoder.hidden/while/body/closed_call/checkpoint/layers/experts/while/body/closed_call/checkpoint/gather_rows/gather_rows/pallas_call", ("experts", "backward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/while/body/closed_call/layers/experts/closed_call/while/body/closed_call/checkpoint/swiglu_rows/swiglu_rows/pallas_call", ("experts", "forward")),
    ("jit(step_fn)/transpose(jvp(SDARDecoder.loss))/SDARDecoder.hidden/while/body/closed_call/checkpoint/layers/experts/while/body/closed_call/checkpoint/rematted_computation/swiglu_rows/swiglu_rows/pallas_call", ("experts", "recompute")),
    ("jit(step_fn)/transpose(jvp(SDARDecoder.loss))/SDARDecoder.hidden/while/body/closed_call/checkpoint/layers/experts/while/body/closed_call/checkpoint/swiglu_rows/swiglu_rows_bwd/pallas_call", ("experts", "backward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/SDARDecoder.hidden/norm/mul", ("lm_head", "forward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/lm_head.loss_sum/while/body/checkpoint/dot_general", ("lm_head", "forward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/lm_head.loss_sum/while/body/checkpoint/block_diffusion_loss/reduce_max", ("loss", "forward")),
    ("jit(step_fn)/jvp(SDARDecoder.loss)/block_diffusion_loss/div", ("loss", "forward")),
])
def test_the_one_table_places_the_familys_scopes(path, want):
    from raft_stereo_tpu.obs import scopes

    assert scopes.component(path, "fusion") == want


# -- the entry point, and the family that was there -----------------------------------


def test_cmd_train_runs_two_token_steps_end_to_end(tmp_path, monkeypatch):
    from raft_stereo_tpu import cli
    from raft_stereo_tpu.utils import run_report as rr

    _, file_config = _configs(4, 2, 1)
    file_config["program"].update(TILES, mixed_precision=True)
    file_config["model_type"] = "sdar_moe"
    path = tmp_path / "config.json"
    path.write_text(json.dumps(file_config))
    monkeypatch.chdir(tmp_path)
    code = cli.cmd_train(["--token_config", str(path), "--seq_len", str(SEQ),
                          "--batch_size", "2", "--num_steps", "2", "--name", "tokens", "--mesh_shape", "1", "1"])
    assert code == rr.EXIT_OK
    report = json.loads((tmp_path / "runs" / "run_report.json").read_text())
    assert report["final_step"] == 2 and report["stop_cause"] == "completed"


def test_token_batches_are_seeded_and_leave_the_mask_row_out():
    from raft_stereo_tpu.data.tokens import TokenBatches

    first = next(iter(TokenBatches(2, SEQ, 4, 95, seed=5)))
    again = next(iter(TokenBatches(2, SEQ, 4, 95, seed=5)))
    assert all(np.array_equal(first[k], again[k]) for k in first)
    assert first["tokens"].shape == (2, SEQ) and first["tokens"].max() < 95
    assert first["masked"].dtype == bool and first["noise_t"].shape == (2, SEQ // 4)
    with pytest.raises(ValueError):
        TokenBatches(2, 30, 4, 95)


def test_family_of_gives_each_config_its_batch():
    from raft_stereo_tpu.train.families import family_of

    stereo = family_of(RAFTStereoConfig(), (32, 48, 3)).batch_shapes(2)
    assert {k: v[0] for k, v in stereo.items()} == {
        "image1": (2, 32, 48, 3), "image2": (2, 32, 48, 3), "flow": (2, 32, 48, 1), "valid": (2, 32, 48)}
    tokens = family_of(_configs()[0], (SEQ,)).batch_shapes(2)
    assert {k: (v[0], np.dtype(v[1]).name) for k, v in tokens.items()} == {
        "tokens": ((2, SEQ), "int32"), "masked": ((2, SEQ), "bool"), "noise_t": ((2, SEQ // 4), "float32")}
    with pytest.raises(ValueError):
        family_of(_configs()[0], (30,))
    with pytest.raises(TypeError):
        family_of(object(), (1,))


# The stereo step as the parent commit lowered it (sha256 of the StableHLO
# text of a tiny trainer's step, recorded on the parent with this very
# function): taking the family out of the trainer changed no operation of it,
# nor did handing the correlation kernels' `shard_map` to `ops/data_axis.py`.
STEREO_STEP_SHA256 = {
    "reg": "2c7fa7a197b3553fe92a3856adbf8d2bcd9a5528bceeabfb14a4f66d77646146",
    "pallas": "4dc0fd5fba444a4d29acdb23178de812247875e823fd036c4488888f4186866e",
}


def stereo_step_text(corr_implementation="reg"):
    from raft_stereo_tpu.obs import scopes
    from raft_stereo_tpu.train.trainer import Trainer

    model = RAFTStereoConfig(hidden_dims=(16, 16, 16), n_gru_layers=1, corr_levels=2, corr_radius=2,
                             corr_implementation=corr_implementation)
    config = TrainConfig(model=model, batch_size=1, train_iters=2, num_steps=10)
    trainer = Trainer(config, sample_shape=(32, 48, 3))
    return trainer.train_step.lower(scopes.abstract(trainer.state), trainer._abstract_batch()).as_text()


@pytest.mark.parametrize("corr_implementation", sorted(STEREO_STEP_SHA256))
def test_the_stereo_step_lowers_to_the_parents_text(corr_implementation):
    text = stereo_step_text(corr_implementation)
    assert hashlib.sha256(text.encode()).hexdigest() == STEREO_STEP_SHA256[corr_implementation]
