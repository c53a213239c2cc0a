"""obs/scopes.py: the component table, the HLO-text parser, the registry, the
scopes a compiled forward and train step actually carry, and the `name=` of
every `pl.pallas_call` in ops/."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.config import RAFTStereoConfig, TrainConfig
from raft_stereo_tpu.models import RAFTStereo
from raft_stereo_tpu.obs import scopes

FWD = "jit(fwd)/RAFTStereo/"
BODY = "jit(step_fn)/transpose(jvp(RAFTStereo))/while/body/closed_call/checkpoint/"


@pytest.mark.parametrize(
    "op_name, opcode, expected",
    [
        (FWD + "cnet/trunk/layer3_0/conv1/conv_general_dilated", "fusion", ("encoder", "forward")),
        (FWD + "fnet/conv2/add", "fusion", ("encoder", "forward")),
        (FWD + "context_zqr_conv1/Conv_0/conv_general_dilated", "convolution", ("encoder", "forward")),
        (FWD + "corr_build/dot_general", "fusion", ("corr_build", "forward")),
        (FWD + "while/body/closed_call/iteration/corr_lookup/corr_lookup/pallas_call", "custom-call",
         ("lookup", "forward")),
        (FWD + "while/body/closed_call/iteration/update_block/encoder/convc1/add", "fusion",
         ("motion_encoder", "forward")),
        (FWD + "while/body/closed_call/iteration/update_block/gru08/tanh", "fusion", ("gru08", "forward")),
        (FWD + "while/body/closed_call/iteration/update_block/gru16/mul", "fusion", ("gru16", "forward")),
        (FWD + "while/body/closed_call/iteration/update_block/gru32/mul", "fusion", ("gru32", "forward")),
        (FWD + "while/body/closed_call/iteration/update_block/flow_head/conv1/max", "fusion",
         ("flow_head", "forward")),
        (FWD + "while/body/closed_call/iteration/update_block/interp_pool/reduce_window_sum", "fusion",
         ("interp_pool", "forward")),
        (FWD + "mask_head/Sequential_0/mask_conv1/add", "fusion", ("mask_head", "forward")),
        (FWD + "upsample/bhwkij,bhwkc->bhiwjc/dot_general", "fusion", ("upsample", "forward")),
        ("jit(step_fn)/jvp(sequence_loss)/reduce_sum", "fusion", ("loss", "forward")),
        ("jit(step_fn)/transpose(jvp(sequence_loss))/mul", "fusion", ("loss", "backward")),
        ("jit(step_fn)/optimizer/grad_clip/mul", "fusion", ("optimizer", "forward")),
        ("jit(step_fn)/grad_clip/sqrt", "fusion", ("optimizer", "forward")),
        (BODY + "iteration/update_block/gru08/transpose", "fusion", ("gru08", "backward")),
        (BODY + "rematted_computation/iteration/update_block/gru08/tanh", "fusion", ("gru08", "recompute")),
        (BODY + "iteration/corr_lookup/corr_scatter/pallas_call", "custom-call", ("lookup", "backward")),
        # the innermost scope wins: the update block's own `encoder` under
        # the model's, an interpolation inside a GRU's argument list
        (FWD + "cnet/while/body/closed_call/iteration/update_block/encoder/add", "fusion",
         ("motion_encoder", "forward")),
        (FWD + "iteration/update_block/gru08/interp_pool/mul", "fusion", ("interp_pool", "forward")),
        # collectives go by opcode, whatever scope they were traced under
        (BODY + "iteration/update_block/gru08/psum", "all-reduce", ("collective", "backward")),
        ("", "all-gather-start", ("collective", "forward")),
        ("", "collective-permute-done", ("collective", "forward")),
        (FWD + "while/body/add", "fusion", ("other", "forward")),
        ("", "copy", ("unscoped", "forward")),
        # merged instructions list several paths; the first decides
        (FWD + "fnet/conv1/reshape;" + FWD + "mask_head/reshape", "fusion", ("encoder", "forward")),
    ],
)
def test_component_table(op_name, opcode, expected):
    assert scopes.component(op_name, opcode) == expected
    assert expected[0] in scopes.COMPONENTS and expected[1] in scopes.PHASES


HLO = '''HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/gru08/add" stack_frame_id=3}
}

ENTRY %main.5 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.7 = f32[4]{0:T(128)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/gru08/add"}
  %custom-call.2 = (bf16[2,8]{1,0}, f32[2]{0}) custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/corr_lookup/corr_scatter/pallas_call" source_file="a \\"quoted\\" path"}
  %copy-done.1 = f32[4]{0} copy-done(%fusion.7)
  ROOT %all-reduce.3 = f32[4]{0} all-reduce(%copy-done.1), replica_groups={}, to_apply=%fused_computation
}
'''


def test_instruction_scopes_reads_every_computation():
    found = scopes.instruction_scopes(HLO)
    assert found["add.1"] == ("jit(f)/gru08/add", "add")
    assert found["fusion.7"] == ("jit(f)/gru08/add", "fusion")
    assert found["custom-call.2"] == ("jit(f)/corr_lookup/corr_scatter/pallas_call", "custom-call")
    assert found["copy-done.1"] == ("", "copy-done")
    assert found["all-reduce.3"] == ("", "all-reduce")
    assert found["Arg_0.1"] == ("x", "parameter")
    assert "main.5" not in found and "fused_computation" not in found
    # a trace event's name is the same line without its metadata
    event = "%fusion.7 = (f32[4]{0:T(128)S(1)}, bf16[2,8]{1,0:T(8,128)(2,1)}) fusion(f32[4]{0} %Arg_0.1), kind=kLoop"
    assert scopes.parse_instruction(event) == ("fusion.7", "fusion", "")
    assert scopes.parse_instruction("$core.py:331 wait") is None


def test_registry_is_lazy_calls_once_and_drops_a_failing_printer():
    scopes.clear()
    calls = collections.Counter()

    def printer():
        calls["good"] += 1
        return HLO

    def broken():
        calls["broken"] += 1
        raise RuntimeError("no backend")

    scopes.register("a/program", printer)
    scopes.register("b/broken", broken)
    assert not calls, "registering must not print anything"
    first = scopes.registered()
    assert set(first) == {"a/program"} and first["a/program"]["fusion.7"][1] == "fusion"
    assert scopes.registered() == first
    assert calls == {"good": 1, "broken": 1}
    # registering the label again replaces what was resolved
    scopes.register("a/program", lambda: "")
    assert scopes.registered() == {"a/program": {}}
    scopes.clear()
    assert scopes.registered() == {}


def test_scoped_decorator_is_safe_to_reenter():
    @scopes.scoped("outer_scope")
    def f(x, depth):
        return x + 1.0 if depth == 0 else f(x, depth - 1) * 2.0

    text = jax.jit(lambda x: f(x, 2)).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "outer_scope/outer_scope/outer_scope/add" in text
    assert "outer_scope/outer_scope/outer_scope/outer_scope" not in text


# -- the scopes of real programs ------------------------------------------------

SMALL = dict(hidden_dims=(32, 32, 32), corr_levels=2, corr_radius=2)
# Instructions that move or name data and compute nothing: a trace shows no
# time under them, and the compiler makes most of them itself, bare.
NO_WORK = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast", "broadcast", "copy"}
FORWARD_COMPONENTS = {
    "encoder", "corr_build", "lookup", "motion_encoder", "gru08", "gru16", "gru32",
    "flow_head", "interp_pool", "mask_head", "upsample",
}


def _census(module):
    counts = collections.Counter()
    for op_name, opcode in module.values():
        if opcode not in NO_WORK:
            counts[scopes.component(op_name, opcode)] += 1
    return counts


@pytest.mark.parametrize("implementation", ["reg", "pallas"])
def test_compiled_forward_carries_every_component(implementation):
    cfg = RAFTStereoConfig(corr_implementation=implementation, **SMALL)
    model = RAFTStereo(cfg)
    image = jax.ShapeDtypeStruct((1, 64, 96, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda a: model.init(jax.random.PRNGKey(0), a, a, iters=1), image)
    fwd = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=2, test_mode=True))
    census = _census(scopes.instruction_scopes(fwd.lower(variables, image, image).compile().as_text()))
    present = {component for component, _ in census}
    assert FORWARD_COMPONENTS <= present, FORWARD_COMPONENTS - present
    assert {phase for _, phase in census} == {"forward"}
    unscoped = sum(n for (component, _), n in census.items() if component == "unscoped")
    assert unscoped < 0.05 * sum(census.values()), census


@pytest.fixture(scope="module")
def train_step_module(tmp_path_factory):
    """The module `Trainer` registers, printed through the registry."""
    from raft_stereo_tpu.train.trainer import Trainer

    scopes.clear()
    tmp = tmp_path_factory.mktemp("scopes_train")
    cfg = TrainConfig(
        model=RAFTStereoConfig(n_gru_layers=1, **SMALL), batch_size=1, num_steps=4, train_iters=2,
        mesh_shape=(1, 1), checkpoint_dir=str(tmp / "ck"), log_dir=str(tmp / "runs"),
    )
    trainer = Trainer(cfg, sample_shape=(32, 48, 3))
    # The printer lowers the very module a fit runs (so its compile is a
    # cache hit): abstract state and batch lower as the placed arrays do.
    batch = {k: np.zeros(v.shape, np.float32) for k, v in trainer._abstract_batch().items()}
    ran = trainer.train_step.lower(trainer.state, trainer.sharding.place_batch(batch)).as_text()
    printed = trainer.train_step.lower(scopes.abstract(trainer.state), trainer._abstract_batch()).as_text()
    assert ran == printed
    del trainer  # the registry must not need it
    module = scopes.registered()["train/step"]
    scopes.clear()
    return module


def test_compiled_train_step_tells_the_phases_apart(train_step_module):
    census = _census(train_step_module)
    by_component = collections.defaultdict(set)
    for component, phase in census:
        by_component[component].add(phase)
    assert {"loss", "optimizer", "encoder", "lookup", "gru08", "upsample"} <= set(by_component)
    assert by_component["gru08"] == {"forward", "backward", "recompute"}
    # what `gru08` recomputes is elementwise: the policy keeps its gate sums
    # (tests/test_remat_saved.py), and a forward convolution stays one here
    convolutions = collections.Counter(
        scopes.component(op_name, opcode)
        for op_name, opcode in train_step_module.values() if opcode == "convolution")
    assert convolutions["gru08", "forward"] > 0 and convolutions["gru08", "recompute"] == 0
    assert {"forward", "backward"} <= by_component["encoder"]
    assert {"forward", "backward"} <= by_component["loss"]
    assert by_component["optimizer"] == {"forward"}
    # The CPU compiler rewrites the backward convolutions into bare
    # reduce-windows, transposes and multiplies (5.05% here); the bound is
    # the one `unscoped_device_pct.*` is held to on the chip.
    unscoped = sum(n for (component, _), n in census.items() if component == "unscoped")
    assert unscoped < 0.10 * sum(census.values()), census


def test_registered_printers_hold_no_array(train_step_module):
    """What `Trainer` and `Evaluator` hand the registry closes over abstract
    shapes and jitted callables only."""
    from raft_stereo_tpu.evaluate import Evaluator

    scopes.clear()
    cfg = RAFTStereoConfig(n_gru_layers=1, **SMALL)
    model = RAFTStereo(cfg)
    image = jnp.zeros((1, 32, 64, 3))
    variables = jax.jit(lambda r: model.init(r, image, image, iters=1))(jax.random.PRNGKey(0))
    evaluator = Evaluator(cfg, variables, iters=1)
    evaluator(np.zeros((30, 60, 3), np.float32), np.zeros((30, 60, 3), np.float32))
    assert set(scopes._pending) == {"evaluate/forward/32x64"}

    def closed_over(fn, seen):
        for cell in fn.__closure__ or ():
            value = cell.cell_contents
            if id(value) in seen:
                continue
            seen.add(id(value))
            yield value
            if callable(value) and getattr(value, "__closure__", None):
                yield from closed_over(value, seen)

    for printer in scopes._pending.values():
        for value in closed_over(printer, set()):
            assert value is not evaluator
            for leaf in jax.tree.leaves(value):
                assert not isinstance(leaf, (jax.Array, np.ndarray)), type(leaf)
    module = scopes.registered()["evaluate/forward/32x64"]
    assert any(scopes.component(*v)[0] == "gru08" for v in module.values())
    scopes.clear()
    # and what it lowers is what ran: abstract arguments mirror commitment
    committed = jax.device_put(image, jax.devices()[0])
    for args in ((variables, image, image), (variables, committed, committed)):
        assert evaluator._fwd.lower(*args).as_text() == evaluator._fwd.lower(*scopes.abstract(args)).as_text()


# -- kernel names -----------------------------------------------------------------


def _lookup_args():
    from raft_stereo_tpu.ops import corr_pallas

    fmap = jnp.ones((1, 4, 16, 8))
    return corr_pallas, corr_pallas.pallas_corr_state(fmap, fmap, 2), jnp.zeros((1, 4, 16))


def _kernel_calls():
    from raft_stereo_tpu.ops import encoder_pallas, gru_tail_pallas

    corr_pallas, state, coords = _lookup_args()
    fmap = jnp.ones((1, 4, 16, 8))
    x = jnp.ones((1, 8, 16, 128), jnp.float32)
    aff = jnp.ones((1, 2, 128), jnp.float32)
    g = jnp.ones((1, 8, 16, 32))
    attention, grouped = _token_kernel_calls()
    return {
        **attention,
        **grouped,
        **_scan_kernel_calls(),
        "corr_lookup": lambda: corr_pallas.pallas_corr_lookup_padded(state, coords, 2),
        "corr_scatter": lambda: jax.grad(
            lambda s: corr_pallas.pallas_corr_lookup_padded(s, coords, 2).sum())(state),
        "corr_lookup_prefetch": lambda: corr_pallas.prefetch_corr_lookup_padded(state, coords, 2),
        "corr_pyramid": lambda: corr_pallas.fused_pyramid_state(fmap, fmap, 2),
        "encoder_conv_s2d": lambda: encoder_pallas.fused_conv_s2d(
            x, jnp.ones((3, 3, 128, 128)), jnp.ones((128,)), aff, affine_form="in", emit_stats=True),
        "encoder_join": lambda: encoder_pallas.fused_join_s2d(x, x, aff, "in"),
        "gru_tail": lambda: gru_tail_pallas.fused_gru_tail(g, g, g, g, g),
        "motion_tail": lambda: gru_tail_pallas.fused_motion_tail(jnp.ones((1, 8, 16, 126)), jnp.ones((1, 8, 16, 1))),
    }


def _scan_kernel_calls():
    """The `granite-hybrid` family's scan kernels at a tiny size: 16
    positions in chunks of 8, 2 heads of 8, a state of 8."""
    from raft_stereo_tpu.ops import ssd_scan as ss

    x, dt, bc = jnp.ones((1, 16, 2, 8)), jnp.ones((1, 16, 2)), jnp.ones((1, 16, 8))
    scan = lambda x: ss.ssd_scan(x, dt, -jnp.ones((2,)), bc, bc, jnp.ones((2,)), chunk=8)[0].sum()
    return {"ssd_chunk": lambda: scan(x), "ssd_chunk_bwd": lambda: jax.grad(scan)(x)}


def _token_kernel_calls():
    """The `sdar-moe` family's kernels at a tiny size: 2 x 16 positions in
    blocks of 4, 2 heads of 8; 16 rows in tiles of 8 over 2 experts."""
    from raft_stereo_tpu.ops import block_attention as ba
    from raft_stereo_tpu.ops import grouped_matmul as gm

    q, kv = jnp.ones((1, 2, 32, 8)), jnp.ones((1, 1, 32, 8))
    attend = lambda q, k, v: ba.block_attention(q, k, v, 16, 4, 8).sum()
    layout = gm.group_layout(jnp.asarray([0, 1, 2, 1] * 4, jnp.int32), 2, 8)
    groups = (layout["tile_expert"], layout["num_tiles"], 8)
    lhs, rhs = jnp.ones((gm.rows_bound(16, 2, 8), 8)), jnp.ones((2, 8, 16))
    product = lambda lhs, rhs: gm.grouped_matmul(lhs, rhs, *groups).sum()
    from raft_stereo_tpu.ops import qk_norm_rope as qk

    prologue = lambda x: qk.qk_norm_rope(x, jnp.ones((8,)), jnp.ones((32, 8)), jnp.zeros((32, 8)), 2, 1e-6).sum()
    slide = lambda q, k, v: ba.window_attention(q, k, v, 4, 1.0, 8).sum()
    attention = {
        "window_attention": lambda: slide(q, kv, kv),
        "window_attention_dq": lambda: jax.grad(slide, 0)(q, kv, kv),
        "window_attention_dkv": lambda: jax.grad(slide, 1)(q, kv, kv),
        "block_attention": lambda: attend(q, kv, kv),
        "block_attention_dq": lambda: jax.grad(attend, 0)(q, kv, kv),
        "block_attention_dkv": lambda: jax.grad(attend, 1)(q, kv, kv),
        "qk_norm_rope": lambda: prologue(jnp.ones((1, 32, 16))),
        "qk_norm_rope_bwd": lambda: jax.grad(prologue)(jnp.ones((1, 32, 16))),
    }
    from raft_stereo_tpu.ops import tile_rows as tr

    source = jnp.where(layout["row_live"], layout["row_source"], -1)
    grouped = {
        "grouped_matmul": lambda: product(lhs, rhs),
        "grouped_matmul_drhs": lambda: jax.grad(product, 1)(lhs, rhs),
        "gather_rows": lambda: tr.gather_rows(jnp.ones((16, 8)), source, layout["num_tiles"], 8),
        "scatter_add_rows": lambda: tr.scatter_add_rows(lhs, source, layout["num_tiles"], 8, 16),
    }
    return attention, grouped


KERNELS = [
    "block_attention", "block_attention_dq", "block_attention_dkv", "qk_norm_rope", "qk_norm_rope_bwd",
    "window_attention", "window_attention_dq", "window_attention_dkv",
    "grouped_matmul", "grouped_matmul_drhs",
    "gather_rows", "scatter_add_rows", "ssd_chunk", "ssd_chunk_bwd",
    "corr_lookup", "corr_scatter", "corr_lookup_prefetch", "corr_pyramid", "encoder_conv_s2d",
    "encoder_join", "gru_tail", "motion_tail",
]


@pytest.mark.parametrize("kernel", KERNELS)
def test_pallas_call_shows_its_name_in_the_lowered_text(kernel):
    call = _kernel_calls()[kernel]
    jaxpr = str(jax.make_jaxpr(call)())
    assert "pallas_call[" in jaxpr and re.search(rf"\bname={kernel}\b", jaxpr)
    text = jax.jit(call).lower().as_text(debug_info=True)
    assert f"/{kernel}/" in text


def test_every_pallas_call_in_ops_is_named():
    import glob
    import os

    import raft_stereo_tpu.ops as ops

    named = set()
    for path in glob.glob(os.path.join(os.path.dirname(ops.__file__), "*.py")):
        source = open(path).read()
        calls = [m.end() for m in re.finditer(r"pl\.pallas_call\(", source)]
        for start in calls:
            depth, i = 1, start
            while depth:
                depth += (source[i] == "(") - (source[i] == ")")
                i += 1
            found = re.search(r'\bname=("(\w+)"|name\b)', source[start:i])
            assert found, f"a pl.pallas_call in {path} has no name="
            if found.group(2):
                named.add(found.group(2))
    # the attention kernels take theirs from the entry's mask: the module's table of them
    from raft_stereo_tpu.ops import block_attention

    named.update(name for names in block_attention.KERNEL_NAMES.values() for name in names)
    assert named == set(KERNELS)


def test_kernel_bytes_do_not_depend_on_who_lowers(monkeypatch):
    """A printer's compile is a cache hit only if the module it lowers is
    byte-identical to the one that ran. A Mosaic kernel's serialized body
    holds its operations' locations; `setup_compile_cache()` keeps the Python
    stack of the caller out of them, and the scopes in every `op_name`."""
    from raft_stereo_tpu.utils import compile_cache

    corr_pallas, state, coords = _lookup_args()
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")  # the directory is not this test's

    def lowered():
        grad = jax.jit(jax.grad(lambda s: corr_pallas.pallas_corr_lookup_padded(s, coords, 2).sum()))
        return grad.trace(state).lower(lowering_platforms=("tpu",)).as_text()

    def from_elsewhere(depth):
        return lowered() if depth == 0 else from_elsewhere(depth - 1)

    before = jax.config.jax_traceback_in_locations_limit
    try:
        with monkeypatch.context() as tpu:
            tpu.setattr(jax, "default_backend", lambda: "tpu")  # lower the kernels for Mosaic
            assert "tpu_custom_call" in lowered()
            assert lowered() != from_elsewhere(3), "the default no longer leaks the stack: drop the setting"
            compile_cache.setup_compile_cache()
            assert jax.config.jax_traceback_in_locations_limit == 1
            assert lowered() == from_elsewhere(3)
        scoped_tanh = jax.jit(scopes.scoped("gru08")(jnp.tanh))
        assert 'op_name="jit(tanh)/gru08/tanh"' in scoped_tanh.lower(jnp.ones(4)).compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", before)
