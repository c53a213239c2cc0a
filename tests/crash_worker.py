"""Subprocess worker for the SIGKILL crash-recovery torture tests
(tests/test_crash_recovery.py).

Runs one or two REAL tiny training legs through the production entry path
(cli.maybe_resume + cli.run_training) over the real DataLoader, with
`auto_resume=True` — so rerunning the worker with the same arguments IS the
documented "restart the same command" recovery. A leg with a crash spec
kills ITSELF with SIGKILL (untrappable: no finally, no atexit, no signal
handler runs) at the configured point:

    none            — run to completion (control run, resume leg)
    before_batch:N  — SIGKILL between steps, just before the batch that
                      would become step N is handed to the trainer
    mid_step:N      — SIGKILL from a timer thread ~0.25 s after handing over
                      the batch for step N (lands inside the jitted step or
                      the surrounding host work)
    mid_save:N      — SIGKILL inside the step-N checkpoint commit, AFTER the
                      orbax items and run_state.json are on disk but BEFORE
                      the integrity manifest — the torn-save window the
                      manifest protocol exists to make survivable
    mid_async_save:N — same torn window, but the commit runs on the
                      AsyncCheckpointCommitter's BACKGROUND thread (requires
                      CRASH_ASYNC_CKPT=1): the kill lands while the step
                      loop is already past N, proving the async protocol
                      keeps the exact PR-3 crash story

With CRASH_ASYNC_CKPT=1 in the environment every leg runs with
`async_checkpoint=True` (the "same command" on rerun includes the flag), so
the resume leg exercises async commits too.

Usage: crash_worker.py <dir1> <spec1> [<dir2> <spec2>]

Two leg pairs run sequentially in ONE process, sharing the compiled train
step via the reset_trainer pattern (tests/fault_injection.py): on this
suite's single-core CPU budget the XLA compile dominates, so the driver
runs "control + kill" as one invocation (the kill leg ends the process;
the control leg has already printed its results) and the resume leg as a
second one. Legs are deterministic, so in-process reuse changes nothing
the assertions depend on.

Every batch handed to the trainer is fingerprinted to an append-only
`<dir>/stream.jsonl` (fsync'd per line so a SIGKILL loses nothing): one
`{"step": S, "fp": F}` record where F identifies the sample (the synthetic
dataset fills each item with its own index). The driver diffs these
against the uninterrupted control leg to prove the resumed stream never
replays or drops a batch window. On leg completion the worker prints
`PARAMSUM <dir> <repr>` (sum of |params|, the trajectory's end-state
fingerprint); the LAST leg's run_training exit code becomes the process
exit code.

The dataset quarantines one permanently-failing sample in the very first
batch, so every leg also carries live quarantine/failure-budget state the
resume must preserve exactly.
"""

import os
import sys

# One CPU device, pinned before jax initializes (same as the other
# subprocess workers). No persistent compilation cache: these legs are
# SIGKILLed at arbitrary points, and a kill must not be able to land inside
# a cache write that the resume leg then reads — the in-process leg reuse
# above already amortizes the compile where it matters. (The heap
# corruption once seen on jax 0.4.37 with a cache hit + orbax restore does
# not reproduce on 0.9.0: six such legs in a row exit 0.)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=1"
).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms", "cpu")

H, W = 32, 48
N_ITEMS = 8
NUM_STEPS = 10      # 8 batches/epoch at batch 1: the resume crosses an epoch
CKPT_EVERY = 2      # saves at 2,4,6,8,10 — several fallback anchors
SEED = 7

# Armed by run_leg for the leg that owns a mid_save spec — the module-level
# write_manifest patch must not fire during a sibling control leg that
# saves the same step numbers.
_KILL = {"kind": None, "step": -1}


def sigkill_self() -> None:
    os.kill(os.getpid(), 9)


class LoggingLoader:
    """Transparent DataLoader proxy that fingerprints every batch handed to
    the trainer (append + fsync, SIGKILL-durable) and injects the
    before_batch / mid_step kills. state_dict/load_state_dict/quarantine
    pass through, so the trainer's run_state save/restore drives the REAL
    loader underneath."""

    def __init__(self, inner, stream_path: str, base_step: int):
        self._inner = inner
        self._stream_path = stream_path
        self._base_step = base_step
        self._handed = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _log(self, step: int, fp: float) -> None:
        with open(self._stream_path, "a") as f:
            f.write('{"step": %d, "fp": %s}\n' % (step, repr(float(fp))))
            f.flush()
            os.fsync(f.fileno())

    def __iter__(self):
        for batch in self._inner:
            self._handed += 1
            step = self._base_step + self._handed
            if _KILL["kind"] == "before_batch" and step == _KILL["step"]:
                sigkill_self()
            self._log(step, batch["image1"][0, 0, 0, 0])
            if _KILL["kind"] == "mid_step" and step == _KILL["step"]:
                import threading

                threading.Timer(0.25, sigkill_self).start()
            yield batch


def parse_crash(spec: str):
    if spec == "none":
        return None
    kind, _, step = spec.partition(":")
    assert kind in ("before_batch", "mid_step", "mid_save", "mid_async_save"), spec
    if kind == "mid_async_save":
        assert os.environ.get("CRASH_ASYNC_CKPT") == "1", (
            "mid_async_save requires CRASH_ASYNC_CKPT=1 (async commits on)"
        )
    return kind, int(step)


def main() -> None:
    legs = [(sys.argv[i], sys.argv[i + 1]) for i in range(1, len(sys.argv), 2)]

    from fault_injection import FaultyItemsDataset, reset_trainer
    from raft_stereo_tpu.cli import maybe_resume, run_training
    from raft_stereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raft_stereo_tpu.data.loader import DataLoader
    from raft_stereo_tpu.train.trainer import Trainer
    from raft_stereo_tpu.utils import checkpoints as ck

    # Kill inside the sidecar commit: orbax items + run_state.json are on
    # disk, the manifest is not — the step must read as torn. Armed per leg.
    orig_write_manifest = ck.write_manifest

    def killing_write_manifest(step_dir, step=None):
        # mid_async_save fires from the committer's background thread (the
        # commit closure resolves ck.write_manifest at call time); SIGKILL
        # from any thread kills the whole process, same torn window.
        if _KILL["kind"] in ("mid_save", "mid_async_save") and step == _KILL["step"]:
            sigkill_self()
        return orig_write_manifest(step_dir, step)

    ck.write_manifest = killing_write_manifest

    # The first sample of epoch 0's shuffled order fails decode forever, so
    # quarantine state exists BEFORE the first checkpoint and must survive
    # every resume (asserted by the driver against the control leg).
    epoch0 = np.random.default_rng((SEED, 0)).permutation(N_ITEMS)
    fail_index = int(epoch0[0])
    print(f"FAIL-INDEX {fail_index}", flush=True)

    base_cfg = TrainConfig(
        model=RAFTStereoConfig(
            hidden_dims=(16, 16, 16), n_gru_layers=1, corr_levels=2, corr_radius=2
        ),
        batch_size=1,
        num_steps=NUM_STEPS,
        train_iters=2,
        mesh_shape=(1, 1),
        name="torture",
        checkpoint_dir="UNSET",
        checkpoint_every=CKPT_EVERY,
        auto_resume=True,
        seed=SEED,
        io_backoff=0.01,
        async_checkpoint=os.environ.get("CRASH_ASYNC_CKPT") == "1",
    )
    trainer = Trainer(base_cfg, sample_shape=(H, W, 3))
    state0 = jax.device_get(trainer.state)

    code = 1
    for workdir, spec in legs:
        crash = parse_crash(spec)
        reset_trainer(
            trainer,
            state0,
            base_cfg,
            checkpoint_dir=os.path.join(workdir, "ck"),
            log_dir=os.path.join(workdir, "logs"),
        )
        loader = DataLoader(
            FaultyItemsDataset(n=N_ITEMS, h=H, w=W, fail_indices=(fail_index,)),
            batch_size=1,
            seed=SEED,
            shuffle=True,
            num_workers=2,
            sample_policy="quarantine",
            sample_retries=0,
            failure_budget=0.5,
        )
        maybe_resume(trainer, trainer.config)  # the production auto-resume path
        base = int(trainer.state.step)
        print(f"START {workdir} step={base}", flush=True)
        if crash:
            _KILL["kind"], _KILL["step"] = crash
        data = LoggingLoader(loader, os.path.join(workdir, "stream.jsonl"), base)
        code = run_training(trainer, data)
        _KILL["kind"] = None
        loader.close()

        report = trainer.last_run_report
        paramsum = float(
            sum(
                np.abs(np.asarray(x)).sum()
                for x in jax.tree.leaves(jax.device_get(trainer.state.params))
            )
        )
        print(f"PARAMSUM {workdir} {paramsum!r}", flush=True)
        print(
            f"RESUMED {workdir} from={report['resumed_from_step']} "
            f"count={report['resume_count']} "
            f"fallback={report['fallback_steps_skipped']}",
            flush=True,
        )
    sys.exit(code)


if __name__ == "__main__":
    main()
