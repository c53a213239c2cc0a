"""Training loop: sharded train step, state, checkpoint/resume.

Replaces the reference training harness (/root/reference/train_stereo.py:133-231):

- `nn.DataParallel` (:137) → a (data, spatial) `jax.sharding.Mesh`; the jitted
  step carries explicit output shardings and XLA inserts the gradient
  all-reduce over ICI.
- AMP GradScaler (:174) → bf16 compute policy; bf16 shares fp32's exponent
  range so no loss scaling is required. Evidenced long-horizon, not just
  asserted (round-4 review weak #3): 600 fresh-data steps under the
  SHIPPING numerics (mixed_precision + Pallas corr + bf16 volume) converge
  to held-out synthetic EPE 0.734 px vs the fp32/reg run's 0.70 px
  (TPU calibration 2026-08-01, `SHIPPING=1 scripts/exp_convergence.py`;
  --runslow variant in tests/test_train.py).
- `torch.save(model.state_dict())` every 500 steps (:203-206) → orbax
  checkpoints of the FULL train state (params + optimizer + step), fixing the
  reference's resume-restarts-the-schedule gap (SURVEY.md §5.3).
- freeze-BN (:170) is structural here: FrozenBatchNorm never consumes batch
  statistics, so `batch_stats` is constant state, not trained.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Dict, Iterable, Optional, Tuple

from flax import struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from raft_stereo_tpu.config import TrainConfig, finalize_train_config
from raft_stereo_tpu.obs import scopes
from raft_stereo_tpu.obs.trace import span
from raft_stereo_tpu.parallel.mesh import make_mesh
from raft_stereo_tpu.parallel.sharding import ShardingEngine
from raft_stereo_tpu.train.families import family_of, make_loss
from raft_stereo_tpu.train.io_spine import AsyncCheckpointCommitter, build_io_spine_block
from raft_stereo_tpu.train.optimizer import make_optimizer

logger = logging.getLogger(__name__)


def is_metrics_host() -> bool:
    """True on the one process that should run in-training validation and
    write metrics (JSONL/TensorBoard). Orbax checkpointing is NOT gated on
    this — its save protocol is collective across processes."""
    return jax.process_index() == 0


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any


def create_train_state(
    config: TrainConfig, rng: jax.Array, sample_shape: Tuple[int, ...]
) -> Tuple[TrainState, optax.GradientTransformation, optax.Schedule]:
    """Initialize model params + optimizer. `sample_shape` is the shape of
    one sample as the model's family reads it (train/families.py): (H, W, C)
    of an image, (L,) tokens; init runs on a batch of 1 (shapes don't affect
    params)."""
    variables = family_of(config.model, sample_shape).init_variables(config, rng)
    tx, schedule = make_optimizer(
        config.lr, config.num_steps, config.wdecay, config.grad_clip_norm
    )
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=variables["params"],
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(variables["params"]),
    )
    return state, tx, schedule


def make_train_step(
    config: TrainConfig,
    tx: optax.GradientTransformation,
    schedule: Optional[optax.Schedule] = None,
):
    """Build the jitted sharded train step. The batch dict and the loss are
    the model family's (train/families.py; for a RAFTStereoConfig:
    image1/image2 (B,H,W,C), flow (B,H,W,1), valid (B,H,W)). The metrics dict
    carries whatever the family's loss reports beside `live_loss` and
    `grad_norm`.

    When `schedule` is given, the per-step learning rate rides the metrics
    dict — the reference Logger writes `learning_rate` every 100 steps
    (/root/reference/train_stereo.py:92,190-191)."""
    family_loss = make_loss(config)

    def step_fn(state: TrainState, batch: Dict[str, jax.Array]):
        def loss_fn(params):
            return family_loss(params, state.batch_stats, batch)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        with jax.named_scope("grad_clip"):
            grad_norm = optax.global_norm(grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
        if config.nan_policy in ("skip", "rollback"):
            # Conditional apply ON DEVICE: a non-finite loss or gradient
            # freezes params and opt_state for this step (the step counter
            # still advances), so a poisoned update can never land no matter
            # how lazily the host polls the `nonfinite` flag
            # (utils/resilience.py NonFiniteGuard does the host-side policy).
            keep = lambda new, old: jnp.where(finite, new, old)
            with jax.named_scope("optimizer"):
                params = jax.tree.map(keep, params, state.params)
                opt_state = jax.tree.map(keep, opt_state, state.opt_state)
        new_state = state.replace(step=state.step + 1, params=params, opt_state=opt_state)
        metrics = dict(metrics, live_loss=loss, grad_norm=grad_norm)
        # Host-side guard flag: 1.0 when this step's loss/grads were NaN/Inf.
        metrics["nonfinite"] = 1.0 - finite.astype(jnp.float32)
        if schedule is not None:
            metrics["learning_rate"] = schedule(state.step)
        return new_state, metrics

    return step_fn


def _capture_host_rng() -> Dict[str, Any]:
    """JSON-able snapshot of the host's legacy global numpy RNG for the
    checkpoint run_state bundle. The loader's own streams are stateless
    (keyed on (seed, epoch, index)), but anything sampling through
    np.random.* — user validate_fns, augment experiments — resumes
    bit-exactly with this restored."""
    name, keys, pos, has_gauss, cached = np.random.get_state()
    return {
        "np_legacy": [name, np.asarray(keys).tolist(), int(pos), int(has_gauss), float(cached)]
    }


def _restore_host_rng(snapshot: Dict[str, Any]) -> None:
    legacy = (snapshot or {}).get("np_legacy")
    if not legacy:
        return
    try:
        name, keys, pos, has_gauss, cached = legacy
        np.random.set_state(
            (name, np.asarray(keys, np.uint32), int(pos), int(has_gauss), float(cached))
        )
    except (ValueError, TypeError):
        # Best-effort by contract: a malformed snapshot (schema drift,
        # hand-edited bundle) must degrade to a warning, not abort the
        # resume it rides in on.
        logger.warning("could not restore host RNG state from checkpoint", exc_info=True)


class Trainer:
    """Owns mesh, state, the compiled step, and checkpointing."""

    def __init__(self, config: TrainConfig, sample_shape: Tuple[int, ...]):
        # Resolve backend-dependent defaults (nan_check_every, coord_interval)
        # once, here — everything downstream sees concrete values.
        self.config = config = finalize_train_config(config)
        # How to initialise and what a batch holds (train/families.py);
        # `sample_shape` is one sample's: (H, W, C) of an image, (L,) tokens.
        self.family = family_of(config.model, sample_shape)
        self.mesh = make_mesh(config.mesh_shape)
        # All in/out shardings, batch placement, and activation constraints
        # come from the rule engine; the `dp` preset reproduces the old
        # hand-wired layout (replicated state, batch over data) exactly.
        self.sharding = ShardingEngine(self.mesh, config.sharding_rules)
        if self.sharding.constrain_activations and not getattr(config.model, "spatial_constraints", True):
            # Spatial presets pin the corr pyramid + GRU hidden state to
            # H-row shards from inside the model (raft_stereo.py). The flag
            # changes no params and no math — only constraint emission — so
            # checkpoints and the init cache key's meaning are unaffected.
            config = dataclasses.replace(
                config,
                model=dataclasses.replace(config.model, spatial_constraints=True),
            )
            self.config = config
        # Init traces the forward too (init_cache jits model.init), so the
        # activation-mesh scope must already be open for constraint emission.
        with self.sharding.scope():
            state, self.tx, self.schedule = create_train_state(
                config, jax.random.PRNGKey(config.seed), sample_shape
            )
        state_shardings = self.sharding.state_shardings(state)
        # place_state routes all-replicated trees through replicate_pytree,
        # not device_put: multi-host device_put onto a replicated sharding
        # broadcasts the whole tree for an equality assert (parallel/mesh.py)
        # — the state is host-identical already.
        self.state = self.sharding.place_state(state)
        self.train_step = self.sharding.wrap(
            jax.jit(
                make_train_step(config, self.tx, self.schedule),
                in_shardings=(state_shardings, self._batch_shardings()),
                out_shardings=(state_shardings, self.sharding.replicated()),
                donate_argnums=(0,),
            )
        )
        self._register_program()
        self._ckpt_mgr = None
        # Async checkpoint commit (train/io_spine.py): with
        # cfg.async_checkpoint the post-snapshot half of each save (orbax
        # flush + sidecar/manifest commit) runs on a background thread. At
        # most one commit is ever in flight — `barrier()` joins and
        # error-checks it before the next save, a rollback restore, and the
        # final synchronous exit save. fit() attaches the live watchdog.
        self._committer = AsyncCheckpointCommitter()
        # Step of the most recent save issued through this Trainer: lets the
        # final fit() save skip a redundant re-save of a step the periodic
        # cadence already wrote (orbax raises on a duplicate step).
        self._last_saved_step: Optional[int] = None
        # What the last fit() absorbed (preemption, skipped steps, rollbacks).
        self.last_run_report: Dict[str, Any] = {}
        # Resume provenance (run_report.json schema v2): which step this
        # process restored at startup (-1/None = fresh), how many times the
        # run chain has resumed (carried through the checkpoint's run_state
        # bundle), and how many torn/corrupt steps auto-resume walked past.
        self.resumed_from_step: Optional[int] = None
        self.resume_count: int = 0
        self.fallback_steps_skipped: int = 0
        # Host-side run state read from the restored checkpoint, applied by
        # the next fit() (which is when the guard/loader objects exist).
        self._pending_run_state: Optional[Dict[str, Any]] = None

    # --- checkpointing (orbax) ---
    def _manager(self):
        if self._ckpt_mgr is None:
            import orbax.checkpoint as ocp

            path = os.path.abspath(os.path.join(self.config.checkpoint_dir, self.config.name))
            self._ckpt_mgr = ocp.CheckpointManager(
                path,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=self.config.max_to_keep,
                    # keep_period additionally pins every Nth step forever —
                    # the sparse long-horizon trail a 100k-step run falls
                    # back on when its recent checkpoints are corrupt.
                    keep_period=self.config.keep_period,
                    create=True,
                ),
            )
        return self._ckpt_mgr

    def checkpoint_path(self) -> str:
        """This run's checkpoint manager root (the --restore_ckpt value that
        resumes it)."""
        return os.path.abspath(os.path.join(self.config.checkpoint_dir, self.config.name))

    def explain_sharding(self) -> str:
        """Every leaf -> PartitionSpec decision for this run's state tree and
        batch layout (the `train --explain_sharding` payload)."""
        return self.sharding.explain(self.state, self._batch_template())

    def _abstract_batch(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """One global batch as abstract shapes (no allocation), each under
        the sharding `place_batch` commits it to: lowered against these, the
        step is the very module a fit runs (a batch without shardings lowers
        to a module that differs in nothing but its name, and misses the
        compile cache)."""
        shardings = self._batch_shardings()
        return {
            name: jax.ShapeDtypeStruct(shape, dtype, sharding=shardings[name])
            for name, (shape, dtype) in self.family.batch_shapes(self.config.batch_size).items()
        }

    def _batch_template(self) -> Dict[str, int]:
        """The family's batch leaves, name -> rank, as the rule engine reads
        a template."""
        shapes = self.family.batch_shapes(self.config.batch_size)
        return {name: len(shape) for name, (shape, _) in shapes.items()}

    def _batch_shardings(self):
        return self.sharding.batch_shardings(self._batch_template())

    def _register_program(self) -> None:
        """Tell obs.scopes how to print the optimized module of the train
        step: the lowering `hlo_audit_record` does, against an abstract state
        (shapes, dtypes, shardings) so that the registry pins no buffer and
        no Trainer. Nothing is lowered until a reader asks."""
        step, batch, state = self.train_step, self._abstract_batch(), scopes.abstract(self.state)
        scopes.register("train/step", lambda: step.lower(state, batch).compile().as_text())

    def hlo_audit_record(self) -> Dict[str, Any]:
        """tools/graftaudit record of THE production train step: lower the
        exact jitted object `fit()` dispatches (same in/out shardings, same
        donate_argnums) against abstract batch shapes and snapshot the
        compiled module. Feeds GA001 (TrainState sharding fixpoint: the
        out_shardings pin proved at the executable level), GA002 (every
        donated state leaf present in input_output_alias) and GA003 (the
        preset's gradient-collective whitelist). Abstract ShapeDtypeStructs
        keep this allocation-free; jit caching means a later fit() on the
        same shapes reuses this very compile."""
        from tools.graftaudit.artifacts import (
            donated_param_numbers,
            snapshot_compiled,
        )

        cfg = self.config
        batch = self._abstract_batch()
        compiled = self.train_step.lower(self.state, batch).compile()
        preset = cfg.sharding_rules
        return snapshot_compiled(
            compiled,
            entry=f"train:step:{preset}",
            kind="train_step",
            preset=preset,
            carry_arg=0,
            carry_out_index=0,
            donated_params=donated_param_numbers((self.state, batch), (0,)),
            meta={
                **self.family.audit_meta(cfg),
                "mesh_shape": list(cfg.mesh_shape),
                "batch_size": cfg.batch_size,
            },
        )

    def _retry_io(self, fn, label: str):
        """Transient-I/O retry wrapper for checkpoint operations — a flaky
        storage blip must not abort a 100k-step run (utils/retry.py)."""
        from raft_stereo_tpu.utils.retry import is_transient_io, retry_call

        return retry_call(
            fn,
            attempts=self.config.io_retries,
            base_delay=self.config.io_backoff,
            classify=is_transient_io,
            label=label,
        )

    def save(self, wait: bool = False, run_state: Optional[Dict[str, Any]] = None):
        """Write a checkpoint and COMMIT it: orbax items first, then the
        `run_state.json` bundle and the integrity `MANIFEST.json` sidecar
        (utils/checkpoints.py) — the manifest's atomic rename is the
        durability point. A kill at any byte before it leaves a step that
        `validate_checkpoint` rejects and auto-resume walks past; after it,
        the step is fully verifiable (per-file sizes + CRC32).

        The manifest can only checksum finished files, so the commit
        sequence always waits for orbax's write before the sidecars. WHERE
        it waits is the `async_checkpoint` knob (train/io_spine.py): on
        this thread (the default — and always with `wait=True`, which the
        rollback anchor and final exit save pass: those must be durable
        before the caller proceeds), or on a background commit thread so
        the step loop runs on while the flush + checksum walk happens off
        the critical path. Either way the device→host snapshot stays on
        the calling thread inside the step-boundary whitelist window, and
        at most one commit is in flight: the barrier below joins (and
        error-checks) the previous one before this save touches the
        manager, preserving the manifest-written-LAST ordering per step."""
        import orbax.checkpoint as ocp

        self._committer.barrier()
        mgr = self._manager()
        step = int(jax.device_get(self.state.step))
        self._retry_io(
            lambda: mgr.save(step, args=ocp.args.StandardSave(self.state)),
            label=f"checkpoint save (step {step})",
        )
        step_dir = os.path.join(self.checkpoint_path(), str(step))
        rs = run_state if run_state is not None else self._minimal_run_state(step)
        process_index = jax.process_index()

        def commit() -> None:
            # `ck` resolved at call time so the crash-torture monkeypatches
            # (tests/crash_worker.py) intercept this sequence on whichever
            # thread runs it — the SIGKILL window is identical sync/async.
            from raft_stereo_tpu.utils import checkpoints as ck

            mgr.wait_until_finished()
            if process_index == 0:
                # The manifest commit is single-writer: the orbax save
                # protocol is collective (every process wrote its shard
                # above), but the manifest covers the whole step dir on
                # shared storage once.
                self._retry_io(
                    lambda: ck.commit_step_sidecars(step_dir, step, rs),
                    label=f"checkpoint manifest commit (step {step})",
                )
            else:
                # Best-effort per-host bundle: quarantine indices are
                # per-shard (each host only sees its own corrupt samples),
                # so each host persists its own view. Manifest-exempt — no
                # cross-process barrier; a kill here degrades to the shared
                # bundle at restore.
                try:
                    ck.write_run_state(step_dir, rs, process_index=process_index)
                except OSError:
                    logger.warning(
                        "could not write per-host run_state for step %d", step, exc_info=True
                    )

        if wait or not self.config.async_checkpoint:
            commit()
        else:
            self._committer.submit(commit, step=step)
        self._last_saved_step = step

    def _minimal_run_state(self, step: int) -> Dict[str, Any]:
        """run_state for saves issued outside fit() (tests, manual saves):
        enough for resume provenance to stay consistent."""
        return {
            "run_state_version": 1,
            "step": int(step),
            "resume_count": int(self.resume_count),
        }

    def restore(
        self,
        step: Optional[int] = None,
        path: Optional[str] = None,
        load_run_state: Optional[bool] = None,
    ):
        """Restore full train state. With `path`, restores from an arbitrary
        orbax checkpoint dir (manager root / step dir / item dir) instead of
        this run's own manager — the reference restores any trained ckpt the
        same way (evaluate_stereo.py:215-219).

        `load_run_state` controls whether the step's run-state bundle —
        loader stream position, quarantine set, NaN/budget counters, host
        RNG — is read and staged for the next fit(), with resume provenance
        (resumed_from_step / resume_count) recorded for run_report.json.
        The default (None) resolves it by intent: True when restoring THIS
        run's own checkpoints (own manager, or a `path` inside this run's
        checkpoint root — a resume), False when warm-starting from another
        run's checkpoint (a donor's loader cursor, quarantine indices, and
        spent failure budget are meaningless — and poisonous — against a
        different dataset/run). The in-loop rollback path passes False
        explicitly: a rollback rewinds the PARAMS timeline but keeps the
        live failure accounting (its rollback/skip counters ARE the
        evidence the report exists to carry)."""
        import orbax.checkpoint as ocp

        from raft_stereo_tpu.utils import checkpoints as ck

        if path is not None:
            if load_run_state is None:
                root = self.checkpoint_path()
                try:
                    load_run_state = (
                        os.path.commonpath([os.path.abspath(path), root]) == root
                    )
                except ValueError:  # different drives (non-posix)
                    load_run_state = False
            item_dir = ck.resolve_orbax_item_dir(path, step)
            restored = self._retry_io(
                lambda: ocp.StandardCheckpointer().restore(item_dir, target=self.state),
                label=f"checkpoint restore ({item_dir})",
            )
            step_dir = os.path.dirname(item_dir)
        else:
            if load_run_state is None:
                load_run_state = True  # own manager: this IS a resume
            mgr = self._manager()
            step = mgr.latest_step() if step is None else step
            if step is None:
                raise FileNotFoundError("no checkpoint to restore")
            restored = self._retry_io(
                lambda: mgr.restore(step, args=ocp.args.StandardRestore(self.state)),
                label=f"checkpoint restore (step {step})",
            )
            # This step verifiably exists in our own manager — the final
            # fit() save can skip re-writing it.
            self._last_saved_step = int(step)
            step_dir = os.path.join(self.checkpoint_path(), str(step))
        self.state = self.sharding.place_state(restored)
        restored_step = int(jax.device_get(self.state.step))
        if load_run_state:
            run_state = ck.read_run_state(step_dir, process_index=jax.process_index())
            self._pending_run_state = run_state
            self.resumed_from_step = restored_step
            prior = int(run_state.get("resume_count", 0)) if run_state else self.resume_count
            self.resume_count = prior + 1
            if run_state is None:
                logger.info(
                    "checkpoint at step %d carries no run_state bundle "
                    "(pre-manifest checkpoint?): weights/optimizer restored; "
                    "data-stream position and failure counters start fresh",
                    restored_step,
                )
        return restored_step

    def auto_resume(self) -> Optional[int]:
        """Crash-consistent resume: scan this run's checkpoint root for the
        newest step whose integrity manifest verifies, quarantine every
        newer torn/corrupt step (renamed `<step>.corrupt-*` so a resumed
        run can re-save those steps cleanly), and restore it — full run
        state included. Returns the restored step; None starts fresh (no
        root or no steps at all). When invalid steps exist but NOTHING
        validates, raises instead: nothing proves those dirs dead, so they
        are not destroyed — and a fresh run would collide with them at its
        first save, after burning a training window.

        This is what makes "rerun the same command" the universal recovery
        for every documented exit code: a SIGKILL at ANY byte leaves either
        a committed manifest (resume there) or a torn step this walks
        past."""
        from raft_stereo_tpu.utils import checkpoints as ck

        root = self.checkpoint_path()
        if not os.path.isdir(root):
            logger.info("auto-resume: no checkpoint root at %s; starting fresh", root)
            return None
        # Every process walks (and agrees on) the anchor — the verdicts are
        # pure functions of the shared checkpoint storage — but only
        # process 0 performs the quarantine renames: N processes racing
        # os.rename on the same dirs would crash all but the winner.
        step, skipped = ck.find_latest_valid_step(
            root, quarantine=jax.process_index() == 0
        )
        self.fallback_steps_skipped = len(skipped)
        if step is None:
            if skipped:
                # Fail FAST, not fresh: the stale invalid step dirs are left
                # in place (no valid anchor proves them dead — they may be a
                # legacy pre-manifest run worth saving), and a fresh run
                # would deterministically collide with them at its first
                # save of the same step number — after burning a whole
                # training window. An immediate actionable error beats a
                # delayed crash loop.
                raise FileNotFoundError(
                    f"auto-resume: no valid checkpoint under {root!r} but "
                    f"{len(skipped)} invalid step dir(s) "
                    f"{[s for s, _ in skipped]} are present (torn saves, or "
                    "a legacy pre-manifest run). Inspect with "
                    "`scripts/fsck_checkpoints.py`, then either quarantine "
                    "them (`--quarantine`) to start this run fresh, or "
                    "point --restore_ckpt at a step you trust."
                )
            logger.info("auto-resume: no checkpoints under %s; starting fresh", root)
            return None
        if skipped:
            logger.warning(
                "auto-resume: fell back past %d invalid step(s) %s to step %d",
                len(skipped), [s for s, _ in skipped], step,
            )
        restored = self.restore(step=step)
        logger.info(
            "auto-resume: restored step %d from %s (resume #%d%s)",
            restored, root, self.resume_count,
            f", {len(skipped)} corrupt step(s) quarantined" if skipped else "",
        )
        return restored

    def rollback(self) -> int:
        """Restore the newest checkpoint in this run's manager — the last
        good state under nan_policy="rollback" (updates from non-finite
        steps never land, so every saved state is finite by construction)."""
        mgr = self._manager()
        # An async commit may still own the newest step: join it (and
        # surface its error) before trusting latest_step() as "last good".
        self._committer.barrier()
        mgr.wait_until_finished()  # the newest save may still be in flight
        latest = mgr.latest_step()
        if latest is None:
            raise FileNotFoundError(
                "rollback requested but no checkpoint exists in "
                f"{self.checkpoint_path()!r}"
            )
        return self.restore(step=latest, load_run_state=False)

    def restore_torch(self, path: str):
        """Load a reference `.pth` (weights only; optimizer restarts — the
        reference behaves the same way, SURVEY.md §5.3)."""
        from raft_stereo_tpu.utils.checkpoints import convert_checkpoint

        variables = convert_checkpoint(path, self.config.model)
        self.state = self.state.replace(
            params=self.sharding.place_state(variables["params"]),
            batch_stats=self.sharding.place_state(variables["batch_stats"]),
        )

    # --- loop ---
    def fit(
        self,
        data: Iterable[Dict[str, np.ndarray]],
        metrics_logger=None,
        validate_fn=None,
    ):
        """Run up to config.num_steps optimization steps over `data`
        (an iterable of host batches; re-iterated when exhausted, mirroring
        the reference's epoch-wrapping while-loop, train_stereo.py:178-226).

        `validate_fn(state) -> {metric: value}` runs every
        config.validate_every steps and logs through `metrics_logger` — the
        in-training validation hook the reference carries but leaves
        commented out (train_stereo.py:208-210, Logger.write_dict
        :120-127).

        Multi-host: every process RUNS validate_fn (the state is laid out
        over the global mesh, so any jitted eval forward is a collective
        program all processes must enter — gating the call itself would
        deadlock the pod at the first validate_every step), but only
        process 0 (`is_metrics_host()`) logs and writes metric rows —
        duplicate JSONL/TB appends from N hosts would corrupt the metric
        history (round-3 review).

        Resilience (utils/resilience.py; knobs on TrainConfig):
        - SIGTERM/SIGINT requests a stop at the next step boundary; the
          final synchronous save below then leaves a restorable checkpoint
          at the interrupted step and the log carries resume instructions.
        - Non-finite loss/grad_norm follows cfg.nan_policy: raise, skip
          (the jitted step already refused the update on device), or
          rollback — after nan_patience consecutive bad steps, restore the
          last good checkpoint and re-iterate `data`, which re-seeds a
          DataLoader's shuffle (fresh epoch) past the offending window.
          Detection fetches the step's `nonfinite` scalar in bulk every
          cfg.nan_check_every steps.
        - Checkpoint saves retry transient I/O (cfg.io_retries); a step the
          periodic cadence already saved is not re-saved at exit.

        Multi-host (parallel/coordination.py): every per-host signal above
        is a POD hazard — one host stopping, rolling back, or raising while
        its peers dispatch the next collective deadlocks the pod. With
        process_count > 1 the loop all-reduces the host flags every
        cfg.coord_interval steps, so stop/rollback/abort branches are taken
        identically on every process at the same step boundary, and the
        loader failure budget is enforced on the POD-global dropped
        fraction. Single-host, the coordinator is an inert fast path that
        dispatches no collective.

        Watchdog (cfg.step_timeout_s > 0): a monitor thread converts a step
        or collective save that stalls past the timeout into all-thread
        stack traces + run_report.json (stop_cause="watchdog") + a non-zero
        exit, instead of an indefinite hang.

        Crash-consistent resume (utils/checkpoints.py): every checkpoint is
        committed by an integrity manifest written LAST and bundles a
        run_state sidecar — loader stream position, quarantine set,
        NaN/rollback counters, pod budget totals, host RNG. A preceding
        restore()/auto_resume() stages that bundle and this fit applies it,
        so a resumed run continues the data stream and failure accounting
        exactly where the checkpoint stopped (torture-proven under SIGKILL
        + byte corruption in tests/test_crash_recovery.py).

        After fit returns (on EVERY exit path — clean, preempted, raised,
        watchdog-killed), `self.last_run_report` holds the machine-readable
        run-health report (utils/run_report.py schema) and the same dict is
        written atomically to <cfg.log_dir>/run_report.json for external
        orchestrators; cli.py maps it onto distinct process exit codes.

        Spans (obs/trace.py; each also a `rs/<name>` event in a profiler
        trace): `train/fit` around the call, with the phases `train/start`
        (entry to the first batch being asked for), `train/steps` (from there
        to the loop's exit), `train/drain` (the wait for the last dispatched
        step — the one device wait the tail keeps) and `train/final_save`;
        inside `train/steps`, per step, `data-wait`, `step` (placement and
        dispatch, not device time), and `checkpoint-save` / `coord-sync`
        where they happen."""
        with span("train/fit"):
            return self._fit(data, metrics_logger, validate_fn)

    def _fit(self, data, metrics_logger, validate_fn):
        """`fit`'s body; the caller holds the `train/fit` span."""
        import contextlib

        from raft_stereo_tpu.obs import (
            Registry,
            Tracer,
            observability_block,
            profile,
            serve_registry,
            set_memory_gauges,
        )
        from raft_stereo_tpu.parallel.coordination import HostCoordinator
        from raft_stereo_tpu.utils import run_report as rr
        from raft_stereo_tpu.utils.jit_hygiene import JitHygiene
        from raft_stereo_tpu.utils.resilience import (
            FailureBudgetExceeded,
            NonFiniteGuard,
            NonFiniteLossError,
            PreemptionGuard,
            StepWatchdog,
        )

        # Re-finalize: tests (and power users) swap host-side knobs on
        # trainer.config between fits; None fields resolve here. Idempotent.
        phase = span("train/start").begin()
        self.config = cfg = finalize_train_config(self.config)
        primary = is_metrics_host()
        step = int(jax.device_get(self.state.step))
        start_step = step
        last_tick: Optional[float] = None  # the previous step's dispatch, for the cadence histogram
        profile_window = (
            range(start_step + 2, start_step + 2 + cfg.profile_steps)
            if cfg.profile_steps
            else range(0)
        )
        profile_ctx = None
        guard = NonFiniteGuard(cfg.nan_policy, patience=cfg.nan_patience)
        pguard = PreemptionGuard()
        coord = HostCoordinator()
        # Jit hygiene (utils/jit_hygiene.py): the recompile monitor always
        # counts (the report block below carries the numbers either way);
        # strict mode additionally runs the loop under
        # transfer_guard("disallow") and hard-fails post-grace compiles.
        hygiene = JitHygiene(strict=cfg.strict_mode, recompile_grace=cfg.recompile_grace)
        # Observability (raft_stereo_tpu/obs): flight recorder + prom
        # registry. Everything here is host-side (perf_counter reads, deque
        # appends, dict updates) — the step loop's zero-sync/zero-executable
        # contract is untouched and asserted with tracing ON in
        # tests/test_obs.py's strict-mode acceptance test.
        tracer = Tracer(
            capacity=cfg.flight_recorder_events,
            dump_path=(
                os.path.join(cfg.log_dir, "flight_recorder.json")
                if cfg.log_dir
                else None
            ),
        )
        registry = Registry()
        step_hist = registry.histogram(
            "raft_train_step_ms", "Wall-clock per-step cadence (tick-to-tick)"
        )
        data_wait_hist = registry.histogram(
            "raft_train_data_wait_ms", "Host wait for the loader between steps"
        )
        steps_counter = registry.counter(
            "raft_train_steps_total", "Optimizer steps dispatched this run"
        )
        metrics_server = serve_registry(registry, cfg.metrics_port) if cfg.metrics_port else None

        def _on_compile(duration_s: float, whitelisted: bool, post_grace: bool) -> None:
            tracer.event(
                "compile",
                duration_s=duration_s,
                whitelisted=whitelisted,
                post_grace=post_grace,
            )

        hygiene.monitor.on_compile = _on_compile
        # Device prefetch (data/prefetch.py): wrap BEFORE the guard/
        # run-state closures bind `data` — the wrapper proxies every loader
        # attribute and serves the stream cursor matching the batch being
        # stepped on, so the checkpoint bundle and budget plumbing cannot
        # tell it from the loader. Its batches arrive already placed on the
        # mesh; the step loop below skips its own place_batch for them.
        prefetcher = None
        batch_keys = tuple(self.family.batch_shapes(cfg.batch_size))
        if cfg.device_prefetch:
            from raft_stereo_tpu.data.prefetch import DevicePrefetcher

            data = prefetcher = DevicePrefetcher(data, self.sharding, hygiene=hygiene, batch_keys=batch_keys)
        quarantine = getattr(data, "quarantine", None)
        if coord.active and hasattr(data, "set_global_budget_mode"):
            # Budget decisions become pod-global: the loader keeps counting
            # but stops raising on its local ratio; the sync below enforces
            # the budget on the all-reduced counts so every host aborts at
            # the same step boundary.
            data.set_global_budget_mode()
        # Pod state mutated by the sync block / read by the report builder.
        pod = {"peer_stop": False}

        # --- crash-consistent resume: apply the restored run_state bundle
        # (utils/checkpoints.py) now that the guard/loader/coordinator
        # objects exist. restore()/auto_resume() staged it; a resumed run
        # then continues the data stream and failure accounting exactly
        # where the checkpoint stopped instead of silently resetting its
        # quarantine set, budget counters, and shuffle position.
        pending = self._pending_run_state
        self._pending_run_state = None
        if pending:
            if pending.get("guard"):
                guard.load_state_dict(pending["guard"])
            if pending.get("loader") and hasattr(data, "load_state_dict"):
                data.load_state_dict(pending["loader"])
            if pending.get("host_rng"):
                _restore_host_rng(pending["host_rng"])
            if coord.active and pending.get("pod"):
                # Pod-global budget totals, all-reduced at save time: adopt
                # them as the pod baseline, with this host's just-restored
                # local counters as its delta baseline, so future syncs
                # reconstruct exact global counts
                # (parallel/coordination.py load_state_dict).
                coord.load_state_dict(
                    pending["pod"],
                    local_dropped=quarantine.dropped if quarantine else 0,
                    local_served=quarantine.served if quarantine else 0,
                )
            logger.info(
                "resumed run state at step %d: loader %s, %d skipped steps, "
                "%d rollbacks, %d quarantined samples (resume #%d)",
                step,
                {k: pending["loader"][k] for k in ("epoch", "batch_cursor")}
                if pending.get("loader") else "n/a",
                guard.skipped_total,
                guard.rollbacks,
                len(quarantine.indices) if quarantine else 0,
                self.resume_count,
            )

        def make_run_state() -> Dict[str, Any]:
            """The host-side state bundled into every checkpoint — the half
            of 'resume' that params/opt/step cannot carry."""
            rs: Dict[str, Any] = {
                "run_state_version": 1,
                "step": step,
                "resume_count": int(self.resume_count),
                "guard": guard.state_dict(),
                "host_rng": _capture_host_rng(),
            }
            if hasattr(data, "state_dict"):
                rs["loader"] = data.state_dict()
            if coord.active:
                rs["pod"] = coord.state_dict()
            return rs

        def make_report(stop_cause, error=None, traces=None, final_step=None):
            # final_step defaults to a device fetch — fine on the normal
            # exit paths where the state is (or will be) materialized. The
            # watchdog path MUST pass a host-side value instead: it fires
            # precisely when device state may never materialize, and a
            # blocking fetch from the monitor thread would hang the very
            # handler that exists to break hangs.
            if final_step is None:
                final_step = int(jax.device_get(self.state.step))
            return rr.build_run_report(
                stop_cause=stop_cause,
                final_step=final_step,
                last_good_step=(
                    self._last_saved_step if self._last_saved_step is not None else -1
                ),
                checkpoint_path=(
                    self.checkpoint_path() if self._last_saved_step is not None else None
                ),
                preempted=pguard.stop_requested or pod["peer_stop"],
                preempt_signal=pguard.signame
                or ("peer" if pod["peer_stop"] else None),
                skipped_steps=guard.skipped_total,
                rollbacks=guard.rollbacks,
                dropped_samples=int(quarantine.dropped) if quarantine else 0,
                quarantined=len(quarantine.indices) if quarantine else 0,
                resumed_from_step=(
                    self.resumed_from_step if self.resumed_from_step is not None else -1
                ),
                resume_count=self.resume_count,
                fallback_steps_skipped=self.fallback_steps_skipped,
                process_index=coord.process_index,
                process_count=coord.process_count,
                coord_syncs=coord.collectives_dispatched,
                watchdog=watchdog.state(),
                jit_hygiene=hygiene.report(),
                io_spine=build_io_spine_block(
                    cfg.async_checkpoint,
                    cfg.device_prefetch,
                    committer=self._committer,
                    prefetcher=prefetcher,
                ),
                observability=observability_block(tracer),
                error=error,
                traces=traces,
            )

        def on_watchdog_timeout(diag):
            # Runs on the monitor thread while the main thread is wedged:
            # persist the verdict BEFORE the hard exit, using only
            # host-side state (no device fetches — see make_report).
            beat_step = watchdog.last_beat_step
            self.last_run_report = make_report(
                "watchdog",
                traces=diag["traces"],
                final_step=beat_step if beat_step is not None else -1,
            )
            rr.write_run_report(self.last_run_report, cfg.log_dir)
            # The watchdog exit is os._exit — no finally runs, so the
            # flight recorder must dump HERE, from the monitor thread.
            tracer.dump("watchdog")

        watchdog = StepWatchdog(
            cfg.step_timeout_s,
            on_timeout=on_watchdog_timeout,
            exit_code=rr.EXIT_WATCHDOG,
            first_grace_s=cfg.watchdog_grace_s,
        )

        def _on_watchdog_fire(diag: Dict[str, Any]) -> None:
            tracer.event(
                "watchdog_fire",
                elapsed_s=float(diag["elapsed_s"]),
                step=diag.get("step"),
                phase=diag.get("phase"),
            )

        watchdog.on_fire = _on_watchdog_fire
        # A wedged background commit blocks the NEXT save's barrier on the
        # main thread; the attached watchdog labels that join
        # ("async-commit-barrier") and grants it the checkpoint allowance,
        # so the hang becomes stack dumps + exit 16, not a silent stall.
        self._committer.attach_watchdog(watchdog, cfg.watchdog_grace_s)
        if validate_fn is not None:
            set_hb = getattr(validate_fn, "set_heartbeat", None)
            if set_hb is not None:
                # Per-image liveness from inside the validator loop: each
                # completed eval forward re-arms the watchdog with the
                # validation allowance, so a LONG validation set (hundreds
                # of images) never trips it while a single hung forward
                # still fires after timeout+grace — a hung validation batch
                # becomes stack traces + exit 16, not a silent stall.
                def _validation_heartbeat():
                    watchdog.beat()
                    watchdog.grant(cfg.watchdog_grace_s)

                set_hb(_validation_heartbeat)

        # Non-finite flags awaiting the host check: (step, device scalar).
        # Fetched in ONE device_get per window so detection doesn't pay a
        # host-device round-trip per step (metrics.py's flush discipline).
        pending_flags: list = []
        # A fatal non-finite verdict held for pod agreement: under
        # coordination one host must not raise while its peers dispatch the
        # next collective, so the error waits for the sync boundary (where
        # every host — the flags being replicated — raises identically).
        fatal: list = []

        def drain_flags(prefetched=None) -> str:
            """Observe the pending non-finite window. `prefetched` carries
            the flag values when the caller already fetched them as part of
            a larger bulk device_get (pod_sync folds this window's fetch
            into the same read as the coordination reduce)."""
            if not pending_flags:
                return "ok"
            flags = (
                jax.device_get([f for _, f in pending_flags])
                if prefetched is None
                else prefetched
            )
            steps_seen = [s for s, _ in pending_flags]
            pending_flags.clear()
            for s, f in zip(steps_seen, flags):
                bad = bool(float(np.asarray(f)) > 0.0)
                if bad:
                    tracer.event("nonfinite", step=s)
                verdict = guard.observe(bad, s)
                if verdict == "rollback":
                    tracer.dump("nonfinite-rollback")
                    # Stop observing: the remaining flags of this window
                    # belong to the timeline the rollback is about to
                    # discard — feeding them to the guard would inflate the
                    # streak/rollback counters past what actually happens.
                    return "rollback"
            return "ok"

        def checked_drain(prefetched=None) -> str:
            """drain_flags, but under active coordination a fatal verdict is
            parked (to be raised once the pod has heard it) instead of
            raised — single-host, it surfaces immediately as before."""
            try:
                return drain_flags(prefetched)
            except NonFiniteLossError as e:
                if not coord.active:
                    raise
                fatal.append(e)
                return "fatal"

        def pod_sync() -> bool:
            """One pod-agreement boundary (in-loop cadence, checkpoint
            refresh, AND the final end-of-run settlement share this):
            reduce the host flags, adopt the pod verdict into the loop
            state, enforce the global budget. Returns whether the pod
            agreed to stop.

            The reduce is SUBMITTED first and its device→host read rides
            the SAME bulk device_get as the pending non-finite flag window
            — a sync adds zero extra host round-trips and zero extra
            executables to the step loop (the carried PR-2 cost question,
            closed; the regression test in tests/test_sharding.py pins
            both). Consequence: verdicts discovered in THIS window (a
            freshly parked fatal, a new rollback wish) reach the pod at the
            NEXT boundary. The local host still refuses checkpoints
            immediately, and acts — raise / roll back — only once the pod
            has heard (fatal_synced / decision.rollback), so no host ever
            abandons its peers mid-collective."""
            nonlocal local_rollback, pod_rollback, fatal_synced
            # Whitelisted: the tiny reduce program compiles once at the
            # first sync — possibly after the grace window.
            with tracer.timed("coord-sync", step=step), hygiene.whitelist("coord_sync"):
                handle = coord.submit(
                    stop=pguard.stop_requested,
                    nonfinite=bool(fatal),
                    rollback=local_rollback,
                    dropped=int(quarantine.dropped) if quarantine else 0,
                    served=int(quarantine.served) if quarantine else 0,
                )
                if fatal:
                    fatal_synced = True
                window = [f for _, f in pending_flags]
                fetched = jax.device_get(window + [handle])
                if checked_drain(prefetched=fetched[: len(window)]) == "rollback":
                    local_rollback = True
                decision = coord.complete(fetched[len(window)])
            watchdog.beat(step)
            if decision.stop and not pguard.stop_requested:
                pod["peer_stop"] = True
            if decision.nonfinite and not fatal:
                fatal.append(
                    NonFiniteLossError(
                        "non-finite divergence on a peer host "
                        f"(pod-coordinated abort at step {step})"
                    )
                )
                # The verdict CAME from the pod — every host heard it.
                fatal_synced = True
            # Adopt the pod verdict: any host's (reported) rollback wish
            # restores ALL hosts (the pod branch must win by construction).
            # A wish born in this very window stays in local_rollback and
            # reaches the pod at the next boundary.
            if decision.rollback:
                pod_rollback = True
            if quarantine is not None:
                quarantine.check_global(
                    decision.dropped, decision.dropped + decision.served
                )
            return decision.stop

        if coord.active and not watchdog.enabled:
            logger.warning(
                "multi-host run with step_timeout_s=0: a host that dies or "
                "force-quits (second signal) mid-collective will hang its "
                "peers indefinitely — set --step_timeout_s so the watchdog "
                "can convert that into a clean exit"
            )
        def waited_batches():
            """One pass over `data`, each wait for a batch under a
            `data-wait` span: host wait between the previous step's boundary
            work and the loader yielding (prefetch miss, disk stall,
            quarantine churn) — the first thing to look at when step cadence
            degrades without device work changing. The wait that finds the
            data exhausted leaves no span."""
            batches = None
            while True:
                with tracer.timed("data-wait", step=step + 1) as wait:
                    try:
                        if batches is None:
                            batches = iter(data)
                        batch = next(batches)
                    except StopIteration:
                        wait.drop()
                        return
                data_wait_hist.observe(wait.seconds * 1e3)
                yield batch

        stop_cause = "completed"
        error_repr = None
        try:
            stopping = False
            local_rollback = False  # this host's rollback wish, not yet pod-agreed
            pod_rollback = False    # pod-agreed rollback awaiting execution
            fatal_synced = False    # the pod has heard this host's parked fatal
            pending_reseed = False  # a rollback is waiting on a fresh data epoch
            with pguard if cfg.handle_signals else contextlib.nullcontext(), watchdog, hygiene.guard():
                if cfg.nan_policy == "rollback" and self._manager().latest_step() is None:
                    # Rollback needs a "last good" anchor before the first
                    # periodic save fires; the initial (or just-restored)
                    # state is it. Inside the try (an unwritable checkpoint
                    # dir must still produce a run_report.json) AND inside
                    # the watchdog context (the save is collective — a dead
                    # peer here must not hang the pod).
                    with hygiene.whitelist("checkpoint_save"):
                        self.save(wait=True, run_state=make_run_state())
                    watchdog.beat(step)
                    # That beat ended the watchdog's first interval — but
                    # the compile-heavy first train step still lies ahead;
                    # re-grant the compile allowance for it.
                    watchdog.grant(cfg.watchdog_grace_s)
                phase.end()
                phase = span("train/steps").begin()
                while step < cfg.num_steps and not stopping:
                    epoch_batches = 0
                    for batch in waited_batches():
                        epoch_batches += 1
                        pending_reseed = False
                        if profile_window and step == profile_window.start:
                            profile_ctx = profile(os.path.join(cfg.log_dir, "profile"))
                            profile_ctx.__enter__()
                        # Dispatch wall only — the device may still be
                        # running (async); a sync here would break the
                        # zero-transfer contract this layer observes.
                        with tracer.timed("step", step=step + 1):
                            if prefetcher is not None:
                                # Already placed on the mesh by the prefetch
                                # thread — while the PREVIOUS step ran.
                                device_batch = batch
                            else:
                                arrays = {k: v for k, v in batch.items() if k in batch_keys}
                                device_batch = self.sharding.place_batch(arrays)
                            self.state, metrics = self.train_step(self.state, device_batch)
                        tick = time.perf_counter()
                        steps_counter.inc()
                        if last_tick is not None:
                            step_hist.observe((tick - last_tick) * 1e3)
                        last_tick = tick
                        step += 1
                        # Step boundary for the recompile monitor: raises
                        # RecompileError (strict mode) when a non-whitelisted
                        # compile landed after the grace window.
                        hygiene.step(step)
                        if profile_ctx is not None and step >= profile_window.stop:
                            jax.block_until_ready(self.state.params)
                            profile_ctx.__exit__(None, None, None)
                            profile_ctx = None
                        pending_flags.append((step, metrics["nonfinite"]))
                        # When a pod sync lands on this same step, leave the
                        # window to pod_sync: it folds this drain's fetch and
                        # the coordination reduce into ONE device_get.
                        sync_due = coord.active and (
                            step % cfg.coord_interval == 0
                            or step % cfg.checkpoint_every == 0
                        )
                        if len(pending_flags) >= cfg.nan_check_every and not sync_due:
                            if checked_drain() == "rollback":
                                local_rollback = True
                        if metrics_logger is not None and primary:
                            # Device arrays go in as-is; the logger fetches once
                            # per log window, keeping step dispatch back-to-back.
                            extra = guard.stats()
                            loader_stats = getattr(data, "resilience_stats", None)
                            if loader_stats is not None:
                                extra.update(loader_stats())
                            metrics_logger.push(dict(metrics, **extra), step)
                        if step % cfg.checkpoint_every == 0:
                            if coord.active:
                                # Refresh the pod-global budget counters with
                                # one extra agreement collective so the
                                # run_state bundle checkpoints all-reduced
                                # totals (and any pending pod verdict is
                                # adopted before committing a checkpoint of a
                                # run a peer already condemned). Same step
                                # boundary on every host by construction.
                                if pod_sync():
                                    stopping = True
                            # Never checkpoint an unchecked non-finite window:
                            # under nan_policy="raise" there is no device-side
                            # update guard, so with nan_check_every > 1 a
                            # deferred detection could otherwise land NaN params
                            # in the checkpoint — and a resume from it would
                            # silently continue a dead run.
                            if not local_rollback and not pod_rollback and not fatal:
                                if checked_drain() == "rollback":
                                    local_rollback = True
                            if not local_rollback and not pod_rollback and not fatal:
                                # Sync saves run the whole flush + manifest
                                # commit here; async saves only the snapshot
                                # (plus the barrier joining the PREVIOUS
                                # commit). Either way, grant the same
                                # allowance validation gets so a large
                                # checkpoint doesn't trip a watchdog sized
                                # for steady steps — a genuinely wedged
                                # save still fires, just later.
                                watchdog.grant(cfg.watchdog_grace_s)
                                watchdog.mark_phase("checkpoint-save")
                                with tracer.timed("checkpoint-save", step=step), hygiene.whitelist("checkpoint_save"):
                                    self.save(run_state=make_run_state())
                                # Save boundary = the memory high-water
                                # sampling point (host-side allocator
                                # introspection, no device work).
                                set_memory_gauges(registry)
                                watchdog.mark_phase(None)
                                watchdog.beat(step)
                        if validate_fn is not None and step % cfg.validate_every == 0:
                            # Validation legitimately dwarfs a steady step
                            # (full eval set + possible compile): grant the
                            # watchdog the compile-grace allowance — renewed
                            # per image by the validation heartbeat above —
                            # and label the phase so a hang report says
                            # "wedged validating", not just "wedged".
                            watchdog.grant(cfg.watchdog_grace_s)
                            watchdog.mark_phase("validation")
                            try:
                                # Whitelisted window: eval forwards compile
                                # per shape bucket and fetch maps to host —
                                # both legitimate here, neither in the loop.
                                with hygiene.whitelist("validation"):
                                    results = validate_fn(self.state)
                            finally:
                                watchdog.mark_phase(None)
                            watchdog.beat(step)
                            if primary:
                                logger.info("validation (%d): %s", step, results)
                                if metrics_logger is not None:
                                    metrics_logger.write(results, step)
                        if pguard.stop_requested and not coord.active:
                            stopping = True
                        # --- pod agreement (multi-host only) -------------
                        synced = False
                        if coord.active and step % cfg.coord_interval == 0:
                            if pod_sync():
                                stopping = True
                            synced = True
                        # A parked fatal raises only once the pod has HEARD it
                        # (fatal_synced): a host that dies before reporting
                        # wedges its peers at the next collective.
                        if fatal and (fatal_synced or not coord.active):
                            raise fatal[0]
                        # Under coordination only the pod-agreed verdict rolls
                        # back (every host adopts it at the same boundary); an
                        # unreported local wish rides the next sync's reduce.
                        want_rollback = pod_rollback if coord.active else local_rollback
                        if want_rollback and (synced or not coord.active):
                            pod_rollback = False
                            local_rollback = False
                            if profile_ctx is not None:
                                # The rewind below can re-cross the profile
                                # window's start; a second start_trace while one
                                # is open would crash the run the rollback is
                                # trying to save. A profile of a NaN-rollback
                                # run is garbage anyway — drop it entirely.
                                profile_ctx.__exit__(None, None, None)
                                profile_ctx = None
                            profile_window = range(0)
                            with hygiene.whitelist("rollback"):
                                step = self.rollback()
                            watchdog.beat(step)
                            pending_reseed = True
                            logger.warning(
                                "rolled back to step %d after %d consecutive "
                                "non-finite steps; re-seeding the data stream",
                                step,
                                cfg.nan_patience,
                            )
                            # Break to a fresh `iter(data)`: a DataLoader derives
                            # its shuffle from the epoch counter, so this walks a
                            # different sample order past the offending window.
                            break
                        watchdog.beat(step)
                        # The next data-wait span begins when the next batch
                        # is asked for, AFTER all boundary work (checkpoint /
                        # validation / sync carry their own spans): it
                        # isolates loader wait instead of re-counting them.
                        if stopping or step >= cfg.num_steps:
                            break
                    if epoch_batches == 0:
                        if pending_reseed:
                            # A rollback broke out expecting a fresh epoch, but
                            # the iterable is one-shot and exhausted — finishing
                            # "gracefully" here would report success on a
                            # NaN-plagued run stuck at the rolled-back step.
                            raise NonFiniteLossError(
                                "rollback could not re-seed the data stream "
                                "(one-shot iterable exhausted); use a re-iterable "
                                "loader with nan_policy=rollback"
                            )
                        if step > start_step:
                            # One-shot iterator exhausted after productive steps:
                            # finish gracefully (final save below) rather than
                            # discarding the progress.
                            break
                        raise ValueError(
                            "data iterable yielded no batches (dataset smaller than "
                            "one global batch, or an exhausted generator was passed)"
                        )
                if profile_ctx is not None:
                    profile_ctx.__exit__(None, None, None)
                phase.end()
                # The one device wait the tail keeps: everything after it
                # (the flag drain, the step fetch, the save) finds the device
                # idle, so `train/steps` + `train/drain` is the time the
                # steps had the device.
                with span("train/drain"):
                    jax.block_until_ready(self.state.params)
                # One FINAL pod sync: every host reaches this point at the
                # same pod-agreed boundary (num_steps or a synced stop), so
                # all dispatch it. It settles anything that happened after
                # the last in-loop sync — a stop signal on one host in the
                # final partial window must still yield ONE pod verdict
                # (every host exits 13, not a 13/0 split the orchestrator
                # can't interpret), and parked fatal/rollback verdicts
                # resolve pod-wide instead of by determinism alone.
                if coord.active:
                    pod_sync()
                # A fatal verdict parked for pod agreement must not outlive
                # the loop — the alternative is saving a checkpoint of a
                # diverged run and reporting exit 0.
                if fatal:
                    raise fatal[0]
                if local_rollback or pod_rollback:
                    # A rollback wish from the final partial window that the
                    # run ended before executing: the state is an unconverged
                    # skip-guarded plateau, not a result. Surface it as the
                    # divergence it is — the report's last_good_step says
                    # where to resume from. (Single-host never parks: the
                    # rollback executes in-loop and training continues.)
                    raise NonFiniteLossError(
                        "non-finite streak triggered a rollback in the final "
                        "coordination window; the run ended before it could "
                        "execute — resume from the last good checkpoint"
                    )
                # Surface a trailing non-finite window before saving. The
                # flags are replicated, so under coordination every host
                # raises (or doesn't) identically — no sync needed here.
                drain_flags()
                if step_hist.count():
                    logger.info(
                        "step timing: p50 %.1f ms, p95 %.1f ms over %d steps (raft_train_step_ms)",
                        step_hist.quantile(0.5), step_hist.quantile(0.95), step_hist.count() + 1,
                    )
                final_step = int(jax.device_get(self.state.step))
                watchdog.grant(cfg.watchdog_grace_s)
                watchdog.mark_phase("final-save")
                try:
                    with span("train/final_save", step=final_step):
                        if self._last_saved_step == final_step and self._ckpt_mgr is not None:
                            # The periodic cadence already saved this exact step
                            # (e.g. num_steps % checkpoint_every == 0) — re-saving
                            # it would make orbax re-write (or reject) a finished
                            # step; just make sure the (possibly async) commit has
                            # landed and was clean before reporting success.
                            self._committer.barrier()
                            self._ckpt_mgr.wait_until_finished()
                        else:
                            with tracer.timed("checkpoint-save", step=final_step, final=True), \
                                    hygiene.whitelist("checkpoint_save"):
                                self.save(wait=True, run_state=make_run_state())
                finally:
                    watchdog.mark_phase(None)
                set_memory_gauges(registry)
                watchdog.beat(final_step)
            if pguard.stop_requested or pod["peer_stop"]:
                stop_cause = "preempted"
                logger.warning(
                    "training stopped by %s at step %d with a synced checkpoint; "
                    "resume by rerunning with --restore_ckpt %s (full train state "
                    "— params, optimizer, and step — restores; the schedule "
                    "continues where it left off)",
                    pguard.signame or "a peer host's stop signal",
                    final_step,
                    self.checkpoint_path(),
                )
        except BaseException as e:
            if isinstance(e, NonFiniteLossError):
                stop_cause = "nonfinite"
            elif isinstance(e, FailureBudgetExceeded):
                stop_cause = "failure_budget"
            elif isinstance(e, KeyboardInterrupt):
                # Second-signal force-quit: still a preemption, but without
                # the graceful final save — last_good_step says what resumes.
                stop_cause = "preempted"
            else:
                stop_cause = "error"
            error_repr = repr(e)
            raise
        finally:
            phase.end()  # a phase an exception cut short still leaves its span
            if not watchdog.fired:
                # The watchdog path wrote its own report from the monitor
                # thread (the main thread never unwinds from a real hang);
                # every other path — clean, preempted, raised — lands here.
                self.last_run_report = make_report(stop_cause, error=error_repr)
                rr.write_run_report(self.last_run_report, cfg.log_dir)
                # Last-N spans next to run_report.json on every exit path
                # this thread survives to see (the watchdog path dumped
                # from the monitor thread before os._exit).
                tracer.dump(f"fit-exit:{stop_cause}")
            if metrics_server is not None:
                metrics_server.shutdown()
                metrics_server.server_close()
                metrics_server._serve_thread.join(timeout=5.0)
        return self.state


# (batch_sharding_tree lived here through PR 8; the rule engine's
# ShardingEngine.batch_shardings emits the identical tree from BATCH_RULES.)
