"""Third rehearsal before a chip run: compile the main path's whole programs
at REAL size for a described (not attached) v5e host — what the chip's
compiler refuses here costs no chip time (`on-chip-measurement` guide,
section 2). Nothing runs: this says nothing about results or times, and a
compile that passes here is never reported as a chip run.

Programs, at the sizes `chip_smoke.py` drives:

  forward   the Evaluator's jitted test-mode forward, 1984x2880, 32 iters
  serve     prelude / chunk / finalize at the 384x1248 bucket, batch 1 and 2
  train     the train step, batch 4, 320x720 crops, 22 iters, one chip
  train-dp  the same step on a (4, 1) data mesh, global batch 8
  train-tokens[-N]  the token family's step as the benchmark's cell runs it
            (benchmark/configs/sdar-30b-a3b-ep8-shard.json, batch 4 x 4096
            tokens), at the file's depth or at N layers; not in the default set
  train-tokens-dp   the same step on a (4, 1) data mesh, global batch 8 (the
            kernels shard_mapped over the data axis); not in the default set

  train-lm[-L]      the hybrid family's step as the benchmark's cell runs it
            (benchmark/configs/granite-4.0-h-micro-pp4-stage.json, batch 1 x
            8192 tokens, or L tokens); not in the default set
  train-laguna[-L[-B]]  the `laguna-moe` family's step as the benchmark's cell
            runs it (benchmark/configs/laguna-xs.2-ep8-shard.json, batch 1 x
            16384 tokens, or B x L); not in the default set

  JAX_PLATFORMS=cpu python scripts/rehearse_chip_compile.py [names...]

The kernel-level compiles (about two seconds each) are tests:
tests/test_chip_compile.py. This script is the slow half (about a minute per
program). Run one such process at a time: describing the topology takes
/tmp/libtpu_lockfile.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, SingleDeviceSharding

from raft_stereo_tpu.config import RAFTStereoConfig, TrainConfig

# The process is on the CPU; the kernels ask jax.default_backend() whether to
# run interpreted. Answer for the described chip — here, not in the program.
jax.default_backend = lambda: "tpu"
jax.config.update("jax_enable_compilation_cache", False)  # unreadable without the chip

MODEL = RAFTStereoConfig(
    corr_implementation="pallas", mixed_precision=True, corr_dtype="bfloat16"
)


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _variables(cfg, sharding):
    from raft_stereo_tpu.models import RAFTStereo

    img = jnp.zeros((1, 64, 96, cfg.in_channels), jnp.float32)
    shapes = jax.eval_shape(
        lambda r: RAFTStereo(cfg).init(r, img, img, iters=1), jax.random.PRNGKey(0)
    )
    return _abstract(shapes, sharding)


def _report(name, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    print(
        f"{name}: compiled in {time.perf_counter() - t0:.0f} s; "
        f"temp {ma.temp_size_in_bytes / 1e9:.2f} GB, "
        f"args {ma.argument_size_in_bytes / 1e9:.2f} GB, "
        f"out {ma.output_size_in_bytes / 1e9:.2f} GB; "
        f"tpu_custom_call x{text.count('custom_call_target=\"tpu_custom_call\"')}",
        flush=True,
    )
    return compiled


def forward(chip):
    from raft_stereo_tpu.evaluate import Evaluator

    variables = _variables(MODEL, chip)
    img = jax.ShapeDtypeStruct((1, 1984, 2880, 3), jnp.float32, sharding=chip)
    ev = Evaluator(MODEL, variables, iters=32)
    _report("forward 1984x2880x32", ev._fwd.lower(variables, img, img))


def serve(chip):
    from raft_stereo_tpu.models.anytime import (
        AnytimeChunk,
        AnytimeFinalize,
        AnytimePrelude,
    )

    variables = _variables(MODEL, chip)
    prelude = jax.jit(AnytimePrelude(MODEL).apply)
    chunk = jax.jit(AnytimeChunk(MODEL, chunk_iters=4).apply)
    finalize = jax.jit(AnytimeFinalize(MODEL).apply)
    for batch in (1, 2):
        img = jax.ShapeDtypeStruct((batch, 384, 1248, 3), jnp.float32, sharding=chip)
        state = _abstract(jax.eval_shape(prelude, variables, img, img), chip)
        _report(f"serve prelude 384x1248 b{batch}", prelude.lower(variables, img, img))
        _report(f"serve chunk(4) 384x1248 b{batch}", chunk.lower(variables, state))
        _report(f"serve finalize 384x1248 b{batch}", finalize.lower(variables, state))


def _published_model(file_name):
    """A token family's model from its configuration's file, the family by
    the file's `model_type`."""
    import json

    from raft_stereo_tpu.config import TOKEN_FAMILIES

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", file_name)) as f:
        published = json.load(f)
    return TOKEN_FAMILIES[published["model_type"]].from_hf_config(published, **published["program"])


def _token_model(layers=None):
    import dataclasses

    model = _published_model("sdar-30b-a3b-ep8-shard.json")
    return dataclasses.replace(model, num_hidden_layers=layers) if layers else model


def _train(name, devices, mesh_shape, batch, model=MODEL, sample=(320, 720, 3)):
    """The Trainer's own step, shardings and trace scope (train/trainer.py
    __init__), on a mesh of described devices instead of jax.devices()."""
    import numpy as np

    from raft_stereo_tpu.train.families import family_of

    from raft_stereo_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS
    from raft_stereo_tpu.parallel.sharding import ShardingEngine
    from raft_stereo_tpu.train.trainer import create_train_state, make_train_step

    cfg = TrainConfig(
        model=model, batch_size=batch, train_iters=22, mesh_shape=mesh_shape
    )
    shapes = family_of(model, sample).batch_shapes(batch)
    mesh = Mesh(np.asarray(devices).reshape(mesh_shape), (DATA_AXIS, SPATIAL_AXIS))
    engine = ShardingEngine(mesh, cfg.sharding_rules)
    made = {}

    def build(rng):
        state, made["tx"], made["schedule"] = create_train_state(cfg, rng, sample)
        return state

    state_shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    state_shardings = engine.state_shardings(state_shapes)
    batch_shardings = engine.batch_shardings({k: len(shape) for k, (shape, _) in shapes.items()})
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state_shapes, state_shardings,
    )
    data = {
        k: jax.ShapeDtypeStruct(shape, dtype, sharding=batch_shardings[k])
        for k, (shape, dtype) in shapes.items()
    }
    step = engine.wrap(
        jax.jit(
            make_train_step(cfg, made["tx"], made["schedule"]),
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, engine.replicated()),
            donate_argnums=(0,),
        )
    )
    compiled = _report(name, step.lower(state, data))
    if len(devices) > 1:
        from raft_stereo_tpu.parallel.sharding import collective_counts

        print(f"{name}: collectives {collective_counts(compiled.as_text())}", flush=True)


PROGRAMS = ("forward", "serve", "train", "train-dp")


def main(names):
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in names or PROGRAMS:
        if name == "forward":
            forward(chip)
        elif name == "serve":
            serve(chip)
        elif name == "train":
            _train("train b4 320x720x22", topo.devices[:1], (1, 1), 4)
        elif name == "train-dp":
            _train("train-dp (4,1) b8 320x720x22", topo.devices, (4, 1), 8)
        elif name == "train-tokens-dp":
            _train("train-tokens-dp (4,1) b8 x 4096", topo.devices, (4, 1), 8, _token_model(), (4096,))
        elif name.startswith("train-lm"):
            seq_len = int(name.split("-")[2]) if name.count("-") == 2 else 8192
            model = _published_model("granite-4.0-h-micro-pp4-stage.json")
            _train(f"train-lm b1 x {seq_len}, {model.num_hidden_layers} layers", topo.devices[:1], (1, 1), 1,
                   model, (seq_len,))
        elif name.startswith("train-laguna"):
            seq_len, batch = ([int(x) for x in name.split("-")[2:]] + [16384, 1])[:2] if name.count("-") > 1 else (16384, 1)
            model = _published_model("laguna-xs.2-ep8-shard.json")
            _train(f"train-laguna b{batch} x {seq_len}, {model.num_hidden_layers} layers", topo.devices[:1], (1, 1),
                   batch, model, (seq_len,))
        elif name.startswith("train-tokens"):
            layers = int(name.split("-")[2]) if name.count("-") == 2 else None
            model = _token_model(layers)
            _train(f"train-tokens b4 x 4096, {model.num_hidden_layers} layers", topo.devices[:1], (1, 1), 4,
                   model, (4096,))
        else:
            raise SystemExit(f"unknown program {name!r}; choose from {PROGRAMS}, train-tokens[-N], train-lm[-L] or train-laguna[-L[-B]]")


if __name__ == "__main__":
    main(sys.argv[1:])
