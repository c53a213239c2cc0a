"""Command-line entry points: train / evaluate / demo / serve.

One CLI with three subcommands replaces the reference's three argparse scripts
whose ~10 architecture flags are copy-pasted (/root/reference/
train_stereo.py:234-272, evaluate_stereo.py:193-208, demo.py:210-228). Flag
names and defaults match the reference so existing launch commands port 1:1;
everything funnels into the typed config dataclasses (config.py).

Usage:
    python -m raft_stereo_tpu train --train_datasets sceneflow ...
    python -m raft_stereo_tpu evaluate --dataset middlebury_F --restore_ckpt ...
    python -m raft_stereo_tpu demo --restore_ckpt ... --root_dataset ...
    python -m raft_stereo_tpu serve --restore_ckpt ... --buckets 384x512 512x768

`train` exits with a distinct documented code per terminal failure class
(utils/run_report.py EXIT_CODES; README "Operations" table): 0 completed,
13 preempted (resume-able), 14 non-finite divergence, 15 failure budget
exceeded, 16 watchdog timeout, 1 anything else, 2 usage — and writes
<log_dir>/run_report.json on every exit path so orchestrators can branch
on machine-readable run health instead of log scraping. With
--auto_resume, rerunning the same command after ANY of those exits
restores the newest integrity-verified checkpoint (full run state — data
stream, quarantine, failure counters) and continues; see README
"Crash-consistent resume".
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
from typing import List, Optional

from raft_stereo_tpu.config import (
    AugmentConfig,
    MODALITIES,
    RAFTStereoConfig,
    SHARDING_PRESETS,
    TrainConfig,
)


def _add_model_args(p: argparse.ArgumentParser):
    """Architecture flags (reference flag table, SURVEY.md §2.4)."""
    p.add_argument("--hidden_dims", nargs="+", type=int, default=[128] * 3)
    p.add_argument(
        "--corr_implementation",
        choices=["reg", "alt", "pallas", "reg_cuda", "alt_cuda"],
        default="reg",
        help="'pallas' is the fused TPU kernel (the reference's reg_cuda role); "
        "the reference's CUDA names are accepted as aliases so its launch "
        "commands (reference README.md:85-88,126-132) port 1:1",
    )
    p.add_argument("--corr_levels", type=int, default=4)
    p.add_argument("--corr_radius", type=int, default=4)
    p.add_argument("--n_downsample", type=int, default=2)
    p.add_argument("--n_gru_layers", type=int, default=3)
    p.add_argument("--slow_fast_gru", action="store_true")
    p.add_argument("--shared_backbone", action="store_true")
    p.add_argument("--mixed_precision", action="store_true")
    p.add_argument(
        "--corr_dtype", choices=["float32", "bfloat16"], default=None,
        help="storage dtype of the precomputed corr pyramid; defaults to "
        "bfloat16 under the reg_cuda alias with --mixed_precision (the "
        "reference's fp16 volume exists only under AMP), float32 otherwise",
    )
    p.add_argument("--data_modality", choices=list(MODALITIES), default="RGB")
    p.add_argument(
        "--fused_encoder",
        action="store_true",
        help="fused Pallas encoder + corr-build kernels for test-mode "
        "forwards (ops/encoder_pallas.py). TPU-only in practice: on the CPU "
        "the kernels run in the Pallas interpreter (pathologically slow at "
        "full resolution); training forwards are unaffected either way",
    )
    p.add_argument(
        "--prefetch_lookup",
        action="store_true",
        help="scalar-prefetch windowed correlation lookup for test-mode "
        "forwards ('pallas' corr only; bit-identical — rough coordinate "
        "fields fall back to the dense kernel). Training forwards are "
        "unaffected; on the CPU it runs in the Pallas interpreter",
    )
    p.add_argument(
        "--fused_gru_tail",
        action="store_true",
        help="fused ConvGRU gate-tail + motion-concat Pallas kernels for "
        "test-mode forwards (ops/gru_tail_pallas.py); training forwards are "
        "unaffected either way",
    )


# The reference's CUDA corr implementations map onto this framework's TPU
# equivalents: reg_cuda (fused CUDA sampler; fp16 volume under AMP) ->
# pallas (fused Pallas lookup; bf16 volume when --mixed_precision — see
# _model_config); alt_cuda (dead in the reference) -> alt.
_CORR_ALIASES = {"reg_cuda": "pallas", "alt_cuda": "alt"}

# Dataset-specific subdir under a parent --root_dataset dir, mirroring the
# validators' own defaults ("datasets/ETH3D" etc., evaluate.py) so train and
# evaluate share one --root_dataset meaning.
_DATASET_SUBDIR = {
    "eth3d": "ETH3D",
    "kitti": "KITTI",
    "things": "",
    "middlebury_F": "Middlebury",
    "middlebury_H": "Middlebury",
    "middlebury_Q": "Middlebury",
}


def _dataset_root(parent: str, dataset: str) -> str:
    return os.path.join(parent, _DATASET_SUBDIR.get(dataset, ""))


def _model_config(args) -> RAFTStereoConfig:
    corr = _CORR_ALIASES.get(args.corr_implementation, args.corr_implementation)
    corr_dtype = args.corr_dtype
    if corr_dtype is None:
        # reg_cuda's reference role is the fp16 corr volume + CUDA sampler —
        # but only under AMP (core/raft_stereo.py:77 autocasts the fmaps, so
        # without --mixed_precision the reference volume stays fp32). Mirror
        # that: bf16 volume only when reg_cuda AND mixed precision.
        corr_dtype = (
            "bfloat16"
            if (args.corr_implementation == "reg_cuda" and args.mixed_precision)
            else "float32"
        )
    return RAFTStereoConfig(
        hidden_dims=tuple(args.hidden_dims),
        corr_implementation=corr,
        corr_dtype=corr_dtype,
        corr_levels=args.corr_levels,
        corr_radius=args.corr_radius,
        n_downsample=args.n_downsample,
        n_gru_layers=args.n_gru_layers,
        slow_fast_gru=args.slow_fast_gru,
        shared_backbone=args.shared_backbone,
        mixed_precision=args.mixed_precision,
        data_modality=args.data_modality,
        fused_encoder=getattr(args, "fused_encoder", False),
        prefetch_lookup=getattr(args, "prefetch_lookup", False),
        fused_gru_tail=getattr(args, "fused_gru_tail", False),
    )


def _load_variables(restore_ckpt: Optional[str], config: RAFTStereoConfig):
    """Restore weights from a torch `.pth` or an orbax checkpoint dir (as
    written by this framework's Trainer), so evaluate/demo run on both
    reference checkpoints and self-trained ones."""
    import jax
    import jax.numpy as jnp

    if restore_ckpt is None:
        return None
    from raft_stereo_tpu.utils.checkpoints import load_variables

    return jax.tree.map(jnp.asarray, load_variables(restore_ckpt, config))


def _token_model_config(args):
    """A token family's model from a published `config.json`-shaped file,
    the family picked by its `model_type` (`sdar_moe`, `granitemoehybrid`,
    `laguna`); a `program` group in it (expert_parallel, expert_shard, block_length,
    ...; benchmark/configs/ has them) sets the chip's share and the program's
    own keys."""
    import json

    from raft_stereo_tpu.config import TOKEN_FAMILIES

    with open(args.token_config) as f:
        published = json.load(f)
    family = TOKEN_FAMILIES.get(published.get("model_type"))
    if family is None:
        raise ValueError(
            f"--token_config: model_type {published.get('model_type')!r} is none of {sorted(TOKEN_FAMILIES)}")
    return family.from_hf_config(published, **published.get("program", {}))


def _train_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="train")
    p.add_argument("--name", default="raft-stereo")
    p.add_argument("--token_config", default=None,
                   help="train a token family instead of RAFT-Stereo, picked "
                   "by the file's model_type: sdar_moe (a routed-expert "
                   "decoder under block diffusion), granitemoehybrid (a "
                   "Mamba-2 / attention hybrid on the next-token loss) or "
                   "laguna (a routed-expert decoder of window and full "
                   "attention layers on the next-token loss): path "
                   "to a config.json-shaped file, optionally with a `program` "
                   "group; batches come from a seeded Zipf source "
                   "(data/tokens.py)")
    p.add_argument("--seq_len", type=int, default=4096,
                   help="tokens a sample (token families only)")
    p.add_argument("--restore_ckpt", default=None)
    p.add_argument("--auto_resume", action="store_true",
                   help="at startup, restore the newest checkpoint of this "
                   "run (checkpoints/<name>) whose integrity manifest "
                   "verifies — walking past and quarantining torn/corrupt "
                   "steps — including the full run state (data-stream "
                   "position, quarantine set, failure counters); with no "
                   "checkpoints the run starts fresh, so rerunning the same "
                   "command is always the correct recovery after any exit")
    p.add_argument("--max_to_keep", type=int, default=5,
                   help="checkpoint retention: keep the newest N steps "
                   "(orbax max_to_keep)")
    p.add_argument("--keep_period", type=int, default=None,
                   help="additionally keep every checkpoint whose step is "
                   "divisible by this, forever — a sparse long-horizon "
                   "fallback trail for 100k-step runs")
    p.add_argument("--batch_size", type=int, default=6)
    p.add_argument("--train_datasets", nargs="+", default=["sceneflow"])
    p.add_argument("--root_dataset", default=None)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--num_steps", type=int, default=100_000)
    p.add_argument("--image_size", type=int, nargs="+", default=[320, 720])
    p.add_argument("--train_iters", type=int, default=16)
    p.add_argument("--valid_iters", type=int, default=32)
    p.add_argument(
        "--valid_datasets", nargs="+", default=[],
        choices=["eth3d", "kitti", "things", "middlebury_F", "middlebury_H", "middlebury_Q"],
        help="run these validators every --validate_every steps during training",
    )
    p.add_argument("--validate_every", type=int, default=500,
                   help="in-training validation cadence (reference "
                   "validation_frequency, train_stereo.py:172)")
    p.add_argument(
        "--valid_pad_bucket", type=int, default=64,
        help="shape-bucket padding for in-training validation (multiple of "
        "32; 0 = exact reference padding, one compile per image shape)",
    )
    p.add_argument("--wdecay", type=float, default=1e-5)
    p.add_argument("--mesh_shape", type=int, nargs=2, default=[-1, 1],
                   help="(data, spatial) device mesh; -1 infers from device count")
    p.add_argument("--sharding_rules", choices=list(SHARDING_PRESETS), default="dp",
                   help="partitioning preset from the rule engine "
                   "(parallel/sharding.py): dp = replicated params, batch "
                   "split over data (the legacy layout, bit-identical); "
                   "spatial = additionally H-shard the cost volume and GRU "
                   "state over the spatial mesh axis; dp+spatial = both; "
                   "fsdp = DP batch layout plus conv kernels (and their "
                   "adam moments) sharded over the data axis")
    p.add_argument("--explain_sharding", action="store_true",
                   help="print every state/batch leaf -> PartitionSpec "
                   "decision the rule engine makes for this config, then "
                   "exit without training")
    p.add_argument("--num_workers", type=int, default=int(os.environ.get("SLURM_CPUS_PER_TASK", 6)) - 2)
    p.add_argument("--worker_type", choices=["thread", "process"], default="thread",
                   help="'process' scales augment past the GIL on many-core hosts")
    # augmentation (reference train_stereo.py:267-271)
    p.add_argument("--img_gamma", type=float, nargs="+", default=None)
    p.add_argument("--saturation_range", type=float, nargs="+", default=None)
    p.add_argument("--do_flip", default=None, choices=["h", "hf", "v"])
    p.add_argument("--spatial_scale", type=float, nargs="+", default=[0, 0])
    p.add_argument("--noyjitter", action="store_true")
    p.add_argument("--profile_steps", type=int, default=0,
                   help="capture a jax.profiler device trace for N steps after warmup")
    # resilience (utils/resilience.py; README "Operations")
    p.add_argument("--nan_policy", choices=["raise", "skip", "rollback"], default="raise",
                   help="non-finite loss/grad policy: fail fast, skip the "
                   "poisoned update, or roll back to the last good checkpoint "
                   "after --nan_patience consecutive bad steps")
    p.add_argument("--nan_patience", type=int, default=10,
                   help="consecutive non-finite steps before skip escalates / "
                   "rollback restores")
    p.add_argument("--nan_check_every", type=int, default=None,
                   help="host-side non-finite detection cadence in steps (one "
                   "bulk device fetch per window); default resolves per "
                   "backend at startup: 1 on CPU, 25 on TPU (each fetch "
                   "is a device-to-host sync there)")
    p.add_argument("--coord_interval", type=int, default=None,
                   help="multi-host coordination cadence in steps (pod-wide "
                   "all-reduce of stop/skip/rollback/budget flags); default "
                   "follows the resolved --nan_check_every; no-op single-host")
    p.add_argument("--step_timeout_s", type=float, default=0.0,
                   help="step watchdog: if a step or collective save stalls "
                   "past this many seconds, dump all-thread stack traces, "
                   "write run_report.json, and exit 16 instead of hanging "
                   "the pod (0 disables; size at ~10x the steady step time)")
    p.add_argument("--watchdog_grace_s", type=float, default=300.0,
                   help="extra watchdog allowance for the first step (XLA "
                   "compile)")
    p.add_argument("--io_retries", type=int, default=3,
                   help="retry attempts for transient checkpoint/dataset I/O "
                   "failures (jittered exponential backoff)")
    p.add_argument("--sample_policy", choices=["raise", "quarantine"], default="quarantine",
                   help="loader reaction to a sample that keeps failing decode: "
                   "abort the epoch, or quarantine + substitute it")
    p.add_argument("--sample_retries", type=int, default=2,
                   help="decode retries per sample before quarantining it")
    p.add_argument("--failure_budget", type=float, default=0.05,
                   help="hard-fail once this fraction of attempted samples has "
                   "been dropped")
    p.add_argument("--no_signal_handlers", action="store_true",
                   help="disable graceful SIGTERM/SIGINT preemption handling")
    # jit hygiene (utils/jit_hygiene.py; README "Developer tooling")
    p.add_argument("--strict_mode", action="store_true",
                   help="run the training loop under "
                   "jax.transfer_guard('disallow') (implicit device<->host "
                   "transfers raise at the offending line; explicit "
                   "device_get/device_put and the whitelisted checkpoint/"
                   "validation windows stay legal) and hard-fail on any XLA "
                   "compile after --recompile_grace steps — proves the step "
                   "loop is transfer-free and recompile-free")
    p.add_argument("--recompile_grace", type=int, default=2,
                   help="steps from start during which compilation is "
                   "expected (initial trace+compile); afterwards a compile "
                   "outside a whitelisted phase fails a --strict_mode run")
    # training I/O spine (train/io_spine.py, data/prefetch.py; README
    # "Operations")
    p.add_argument("--async_checkpoint", action="store_true",
                   help="run the post-snapshot half of each checkpoint save "
                   "(orbax flush + run_state/manifest commit) on a "
                   "background thread; the device snapshot stays at the "
                   "step boundary, at most one commit is in flight (a "
                   "barrier joins it before the next save / a rollback / "
                   "the final exit save), and the manifest is still written "
                   "LAST — a SIGKILL mid-commit leaves a torn step that "
                   "--auto_resume and fsck_checkpoints.py skip, exactly as "
                   "with sync saves")
    p.add_argument("--device_prefetch", action="store_true",
                   help="stage batch N+1 on the device mesh while step N "
                   "runs (maxsize-1 double buffer around the loader; zero "
                   "new executables, batch-exact resume preserved); overlap "
                   "health lands in run_report.json's io_spine block")
    # observability (raft_stereo_tpu/obs; README "Observability")
    p.add_argument("--metrics_port", type=int, default=0,
                   help="start a stdlib HTTP sidecar on this port exposing "
                   "step-time/data-wait histograms and device-memory gauges "
                   "as Prometheus text at GET /metrics (0 disables)")
    p.add_argument("--flight_recorder_events", type=int, default=256,
                   help="flight-recorder ring capacity: the last N trace "
                   "spans/events dumped as <log_dir>/flight_recorder.json "
                   "on watchdog fire, non-finite rollback, and every fit "
                   "exit (0 disables recording; counters still report)")
    p.add_argument("--compilation_cache_dir", default=None, metavar="DIR",
                   help="persistent JAX compilation cache directory: compiled "
                   "programs are written under DIR and reused across "
                   "restarts/preemptions, so --auto_resume relaunches skip "
                   "the multi-minute XLA compile. Default: .jax_cache/ in the "
                   "checkout. JAX_COMPILATION_CACHE_DIR, when set, wins over "
                   "both (utils/compile_cache.py)")
    _add_model_args(p)
    return p


def maybe_resume(trainer, config) -> Optional[int]:
    """Startup restore policy, shared by cmd_train and the crash-torture
    worker (tests/crash_worker.py) so the tested recovery path IS the
    production one. Precedence: `--auto_resume` first (this run's OWN newest
    valid checkpoint — the restart-the-same-command contract), then
    `--restore_ckpt` (an explicit warm start from another run or a torch
    `.pth`; the run-state bundle is only adopted when the path points back
    into this run's own checkpoint root — a donor run's loader cursor and
    failure counters must not leak into a fresh run, Trainer.restore). A
    fresh auto-resume (no checkpoints yet) falls through to restore_ckpt,
    so `--auto_resume --restore_ckpt <pretrained>` means "warm-start once,
    then self-resume forever after". Returns the restored step, or None
    when starting from scratch."""
    if config.auto_resume:
        step = trainer.auto_resume()
        if step is not None:
            return step
    if config.restore_ckpt:
        if config.restore_ckpt.endswith(".pth"):
            trainer.restore_torch(config.restore_ckpt)
            return None  # weights only; the step counter starts at 0
        return trainer.restore(path=config.restore_ckpt)
    return None


def run_training(trainer, loader, metrics_logger=None, validate_fn=None) -> int:
    """Drive trainer.fit and translate its outcome into the documented
    process exit code (utils/run_report.py EXIT_CODES), so an external
    orchestrator can tell "preempted, resume me" (13) from "diverged, page
    a human" (14) from "data rotting past the failure budget" (15) without
    parsing logs. The trainer itself writes run_report.json on every exit
    path — including these raising ones — before this mapping runs; a
    watchdog timeout never reaches here (the monitor thread hard-exits 16
    after writing its own report). Shared by cmd_train and the multi-host
    fault-injection workers (tests/coordination_worker.py) so the tested
    exit path IS the production one."""
    import traceback

    from raft_stereo_tpu.utils import run_report as rr
    from raft_stereo_tpu.utils.resilience import (
        FailureBudgetExceeded,
        NonFiniteLossError,
    )

    try:
        trainer.fit(loader, metrics_logger=metrics_logger, validate_fn=validate_fn)
    except (NonFiniteLossError, FailureBudgetExceeded, KeyboardInterrupt) as e:
        logging.getLogger(__name__).error(
            "training aborted: %r\n%s", e, traceback.format_exc()
        )
        # fit's finally block already classified the exception into
        # last_run_report (stop_cause -> EXIT_CODES) — read the verdict
        # instead of maintaining a second mapping table here.
        report = getattr(trainer, "last_run_report", None) or {}
        return int(report.get("exit_code", rr.EXIT_ERROR))
    report = trainer.last_run_report
    return rr.EXIT_PREEMPTED if report.get("preempted") else rr.EXIT_OK


def cmd_train(argv: List[str]) -> int:
    args = _train_parser().parse_args(argv)

    from raft_stereo_tpu.utils import run_report as rr

    try:
        config = _train_config_from_args(args)
    except Exception as e:
        # Config validation failures must also leave a run_report.json (the
        # "any launch that got as far as the train command" contract); the
        # config never materialized, so the report lands in the DEFAULT
        # log dir.
        logging.getLogger(__name__).exception("invalid training configuration")
        default_log_dir = TrainConfig.__dataclass_fields__["log_dir"].default
        rr.write_run_report(
            rr.build_run_report(stop_cause="error", final_step=-1, error=repr(e)),
            default_log_dir,
        )
        return rr.EXIT_ERROR
    return _run_train(args, config)


def _train_config_from_args(args) -> TrainConfig:
    return TrainConfig(
        model=_token_model_config(args) if args.token_config else _model_config(args),
        augment=AugmentConfig(
            crop_size=tuple(args.image_size),
            min_scale=args.spatial_scale[0],
            max_scale=args.spatial_scale[1],
            do_flip=args.do_flip,
            yjitter=not args.noyjitter,
            saturation_range=tuple(args.saturation_range) if args.saturation_range else None,
            img_gamma=tuple(args.img_gamma) if args.img_gamma else None,
        ),
        name=args.name,
        batch_size=args.batch_size,
        train_datasets=tuple(args.train_datasets),
        lr=args.lr,
        num_steps=args.num_steps,
        train_iters=args.train_iters,
        valid_iters=args.valid_iters,
        wdecay=args.wdecay,
        restore_ckpt=args.restore_ckpt,
        auto_resume=args.auto_resume,
        max_to_keep=args.max_to_keep,
        keep_period=args.keep_period,
        root_dataset=args.root_dataset,
        mesh_shape=tuple(args.mesh_shape),
        sharding_rules=args.sharding_rules,
        num_workers=args.num_workers,
        worker_type=args.worker_type,
        profile_steps=args.profile_steps,
        validate_every=args.validate_every,
        nan_policy=args.nan_policy,
        nan_patience=args.nan_patience,
        nan_check_every=args.nan_check_every,
        coord_interval=args.coord_interval,
        step_timeout_s=args.step_timeout_s,
        watchdog_grace_s=args.watchdog_grace_s,
        io_retries=args.io_retries,
        sample_policy=args.sample_policy,
        sample_retries=args.sample_retries,
        failure_budget=args.failure_budget,
        handle_signals=not args.no_signal_handlers,
        strict_mode=args.strict_mode,
        recompile_grace=args.recompile_grace,
        async_checkpoint=args.async_checkpoint,
        device_prefetch=args.device_prefetch,
        metrics_port=args.metrics_port,
        flight_recorder_events=args.flight_recorder_events,
        compilation_cache_dir=args.compilation_cache_dir,
    )


def _run_train(args, config: TrainConfig) -> int:
    from raft_stereo_tpu.utils import run_report as rr

    try:
        from raft_stereo_tpu.data.datasets import build_training_dataset
        from raft_stereo_tpu.data.loader import DataLoader
        from raft_stereo_tpu.parallel.distributed import host_shard_args, init_multihost
        from raft_stereo_tpu.train.trainer import Trainer
        from raft_stereo_tpu.utils.compile_cache import setup_compile_cache
        from raft_stereo_tpu.utils.metrics import MetricsLogger

        setup_compile_cache(config.compilation_cache_dir)
        init_multihost()  # no-op single-host; connects the pod otherwise
        if args.token_config:
            trainer, loader = _token_trainer(args, config)
            if getattr(args, "explain_sharding", False):
                print(trainer.explain_sharding())
                return 0
            maybe_resume(trainer, config)
            return run_training(
                trainer, loader,
                metrics_logger=MetricsLogger(log_every=config.log_every, log_dir=config.log_dir),
            )
        if getattr(args, "explain_sharding", False):
            # Dry run: initialize the state tree and dump every leaf ->
            # PartitionSpec decision, without touching datasets or ckpts.
            h, w = config.augment.crop_size
            trainer = Trainer(config, sample_shape=(h, w, config.model.in_channels))
            print(trainer.explain_sharding())
            return 0
        dataset = build_training_dataset(config, config.model.data_modality)
        loader = DataLoader(
            dataset,
            config.batch_size,
            seed=config.seed,
            num_workers=config.num_workers,
            worker_type=config.worker_type,
            sample_policy=config.sample_policy,
            sample_retries=config.sample_retries,
            failure_budget=config.failure_budget,
            **host_shard_args(),
        )
        h, w = config.augment.crop_size
        trainer = Trainer(config, sample_shape=(h, w, config.model.in_channels))
        maybe_resume(trainer, config)
        validate_fn = None
        if args.valid_datasets:
            from raft_stereo_tpu.evaluate import make_validation_fn

            # --root_dataset is the PARENT datasets dir (build_training_dataset
            # semantics); each validator's `root` is its dataset-specific subdir,
            # matching the validators' own defaults ("datasets/ETH3D" etc.).
            vkw = (
                {
                    name: {"root": _dataset_root(args.root_dataset, name)}
                    for name in args.valid_datasets
                }
                if args.root_dataset
                else None
            )
            validate_fn = make_validation_fn(
                config.model,
                args.valid_datasets,
                iters=config.valid_iters,
                validator_kwargs=vkw,
                pad_bucket=args.valid_pad_bucket,
            )
    except Exception as e:
        # The previously-silent exception path: a failure BEFORE the trainer
        # exists (bad dataset path, checkpoint mismatch, config error) used
        # to exit with only a traceback — no run_report.json for the
        # orchestrator. The trainer covers every fit() exit path itself;
        # this covers everything up to it.
        logging.getLogger(__name__).exception("training setup failed")
        rr.write_run_report(
            rr.build_run_report(stop_cause="error", final_step=-1, error=repr(e)),
            config.log_dir,
        )
        return rr.EXIT_ERROR
    return run_training(
        trainer,
        loader,
        metrics_logger=MetricsLogger(log_every=config.log_every, log_dir=config.log_dir),
        validate_fn=validate_fn,
    )


def _token_trainer(args, config: TrainConfig):
    """A token family's trainer and its loader: the same `Trainer`, a seeded
    token source (data/tokens.py), no validation set."""
    from raft_stereo_tpu.data.tokens import TokenBatches
    from raft_stereo_tpu.train.trainer import Trainer

    model = config.model
    block_length = getattr(model, "block_length", 0)  # 0: ids alone, no noise (the causal families)
    rows = model.vocab_size
    if block_length and model.mask_token_id == model.vocab_size - 1:
        rows = model.mask_token_id  # the mask token's row is never data
    loader = TokenBatches(config.batch_size, args.seq_len, block_length, rows, seed=config.seed)
    return Trainer(config, sample_shape=(args.seq_len,)), loader


def cmd_evaluate(argv: List[str]) -> int:
    p = argparse.ArgumentParser(prog="evaluate")
    p.add_argument("--restore_ckpt", default=None)
    p.add_argument(
        "--dataset",
        required=True,
        choices=["eth3d", "kitti", "things"] + [f"middlebury_{s}" for s in "FHQ"],
    )
    p.add_argument("--valid_iters", type=int, default=32)
    p.add_argument(
        "--root_dataset", default=None,
        help="parent datasets directory (same semantics as train: the "
        "dataset-specific subdir, e.g. ETH3D/, is appended automatically)",
    )
    p.add_argument(
        "--pad_bucket", type=int, default=0,
        help="round padded eval shapes up to a multiple of this (0 = exact "
        "reference ÷32 padding); mixed-size sets then reuse a few compiles",
    )
    p.add_argument(
        "--dry_run", action="store_true",
        help="run the full evaluate path (checkpoint load, validator loop, "
        "padding, jitted forward, metric math) on a tiny synthetic dataset "
        "instead of downloaded data — the README runbook's smoke test",
    )
    _add_model_args(p)
    args = p.parse_args(argv)

    import jax

    from raft_stereo_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    config = _model_config(args)
    from raft_stereo_tpu.evaluate import VALIDATORS, Evaluator

    variables = _load_variables(args.restore_ckpt, config)
    if variables is None:
        # Cached per-config jitted init (models/init_cache.py): building a
        # fresh jax.jit wrapper here re-compiled flax init on EVERY
        # invocation — a fresh jit object is a fresh compile cache
        # (regression-asserted via RecompileMonitor in
        # tests/test_jit_hygiene.py).
        from raft_stereo_tpu.models import init_model_variables

        variables = init_model_variables(config)

    n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
    print(f"The model has {n_params/1e6:.2f}M learnable parameters.")

    evaluator = Evaluator(config, variables, iters=args.valid_iters, pad_bucket=args.pad_bucket)
    kwargs = {}
    if args.dry_run:
        from raft_stereo_tpu.evaluate import SyntheticEvalDataset

        kwargs["dataset"] = SyntheticEvalDataset(channels=config.in_channels)
    elif args.root_dataset:
        # Same parent-dir semantics as cmd_train's --valid_datasets wiring,
        # so one --root_dataset value works across both commands.
        kwargs["root"] = _dataset_root(args.root_dataset, args.dataset)
    VALIDATORS[args.dataset](evaluator, **kwargs)
    return 0


# Admin-client exit codes (`serve --reload_ckpt`, `frontier --rollout`):
# distinct and stable so operator scripts can branch without parsing
# stderr. 0 = done; 1 = server answered an error; 3 = refused
# (409: checkpoint mismatch / rollout already running / mixed fleet);
# 4 = could not connect; 5 = connected but the response stalled past the
# timeout; 6 = the server answered bytes that are not JSON.
EXIT_ADMIN_HTTP_ERROR = 1
EXIT_ADMIN_REFUSED = 3
EXIT_ADMIN_UNREACHABLE = 4
EXIT_ADMIN_TIMEOUT = 5
EXIT_ADMIN_BAD_BODY = 6


def _admin_post_client(
    url: str, payload: dict, what: str, timeout_s: float
) -> int:
    """Shared POST-and-report client for the serving admin endpoints.
    Maps every failure mode to a distinct exit code and a one-line
    message — an operator mid-incident should never see a traceback for
    'the server is down'."""
    import json

    from raft_stereo_tpu.utils.http import request_json

    try:
        resp = request_json(url, method="POST", payload=payload,
                            timeout_s=timeout_s)
    except TimeoutError as exc:
        # Before ConnectionError/OSError: TimeoutError subclasses OSError,
        # and a stalled response is actionable differently from a dead
        # server (the swap may still be in progress server-side).
        print(f"{what}: no response from {url} within {timeout_s:.0f}s "
              f"({exc}) — the server may still be applying it; check "
              "/healthz before retrying", file=sys.stderr)
        return EXIT_ADMIN_TIMEOUT
    except (ConnectionError, OSError) as exc:
        print(f"{what}: cannot reach {url} ({exc}) — is the server "
              "running?", file=sys.stderr)
        return EXIT_ADMIN_UNREACHABLE
    try:
        body = resp.json()
        if not isinstance(body, dict):
            raise ValueError("response is not a JSON object")
    except Exception as exc:  # noqa: BLE001 - any decode failure
        print(f"{what}: {url} answered status {resp.status} with a "
              f"non-JSON body ({exc}): {resp.body[:200]!r}",
              file=sys.stderr)
        return EXIT_ADMIN_BAD_BODY
    rendered = json.dumps(body, indent=2, sort_keys=True)
    if resp.ok:
        print(rendered)
        return 0
    print(f"{what}: {url} answered {resp.status}", file=sys.stderr)
    print(rendered, file=sys.stderr)
    return EXIT_ADMIN_REFUSED if resp.status == 409 else EXIT_ADMIN_HTTP_ERROR


def _reload_checkpoint_client(
    host: str, port: int, ckpt: str, timeout_s: float = 600.0
) -> int:
    """`serve --reload_ckpt PATH`: ask a RUNNING server to hot-swap its
    weights via POST /reload and report the outcome. The path is resolved
    server-side, so it must be visible to the server process. Uses the
    shared stdlib client (utils/http.py) — the same timeout discipline
    the frontier follows."""
    return _admin_post_client(
        f"http://{host}:{port}/reload",
        {"checkpoint": ckpt},
        "reload",
        timeout_s,
    )


def _rollout_client(
    host: str,
    port: int,
    ckpt: str,
    rollback_ckpt: Optional[str],
    force: bool,
    timeout_s: float = 3600.0,
) -> int:
    """`frontier --rollout PATH`: drive a RUNNING frontier's POST
    /rollout and report the full rollout record. A long default timeout —
    the call returns only when the whole fleet walk (or its rollback)
    finishes."""
    payload: dict = {"checkpoint": ckpt}
    if rollback_ckpt is not None:
        payload["rollback_checkpoint"] = rollback_ckpt
    if force:
        payload["force"] = True
    return _admin_post_client(
        f"http://{host}:{port}/rollout", payload, "rollout", timeout_s
    )


def cmd_serve(argv: List[str]) -> int:
    p = argparse.ArgumentParser(prog="serve")
    p.add_argument("--restore_ckpt", default=None)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument(
        "--buckets", nargs="+", default=["384x512", "512x768"],
        help="padded HxW shape buckets (each dim a multiple of 32); requests "
        "are admitted into the smallest bucket that fits, larger inputs are "
        "rejected with 413 — every listed bucket is compiled at boot",
    )
    p.add_argument("--max_batch", type=int, default=4,
                   help="micro-batch ceiling; batch sizes 1,2,...,max_batch "
                   "(powers of two) are warmed per bucket")
    p.add_argument("--chunk_iters", type=int, default=4,
                   help="GRU iterations per jitted chunk — the deadline-check "
                   "granularity")
    p.add_argument("--max_iters", type=int, default=32,
                   help="refinement budget when no deadline intervenes "
                   "(rounded up to whole chunks)")
    p.add_argument("--deadline_ms", type=float, default=0.0,
                   help="default per-request deadline (0 disables; requests "
                   "can override per call)")
    p.add_argument("--batch_window_ms", type=float, default=2.0,
                   help="how long a partial batch waits for company before "
                   "dispatching")
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas, one per local device: each holds "
                   "its own committed weight copy, warmed executables and "
                   "lifecycle breaker (one chip = one fault domain; a "
                   "failed/hung replica's batch is requeued onto a healthy "
                   "one, and POST /reload rolls replicas one at a time). "
                   "0 = one per visible device; requires "
                   "--sharding_rules dp; 1 keeps the single-engine path")
    p.add_argument("--sharding_rules", choices=list(SHARDING_PRESETS), default="dp",
                   help="partitioning preset for the serving executables: "
                   "'spatial' / 'dp+spatial' warm per-bucket programs with "
                   "the cost volume and GRU state H-sharded over all local "
                   "devices (single-chip and 'dp' keep the legacy layout)")
    p.add_argument("--warmup_only", action="store_true",
                   help="warm every (bucket, batch) executable, print the "
                   "warmup summary, and exit — a boot-time smoke test")
    p.add_argument("--aot_cache_dir", default=None, metavar="DIR",
                   help="persistent AOT executable cache: warmed executables "
                   "are serialized under DIR keyed on (jaxlib version, "
                   "backend/topology, buckets, model config); the next boot "
                   "deserializes instead of tracing+compiling, cutting "
                   "restart-to-serving to seconds (corrupt or "
                   "version-mismatched entries are evicted loudly and "
                   "recompiled — never a boot failure)")
    p.add_argument("--require_cache_hit", action="store_true",
                   help="with --warmup_only: exit nonzero unless EVERY warmup "
                   "entry was served from --aot_cache_dir (zero traces) — "
                   "the CI gate that catches accidental cache-key churn "
                   "before it slows production restarts")
    p.add_argument("--audit", action="store_true",
                   help="HLO contract audit (tools/graftaudit): snapshot "
                   "every executable this boot warms — AOT cache hits "
                   "replay the snapshot stored with the entry — and check "
                   "the GA001-GA005 contracts (reshard-free chunk "
                   "boundaries, collective whitelists, bf16 corr pins, "
                   "hot-path purity); the summary JSON gains an "
                   "\"hlo_audit\" block, and with --warmup_only any "
                   "violation exits 4")
    p.add_argument("--auto_respawn", action="store_true",
                   help="fleet self-healing: when a replica's breaker goes "
                   "sticky-'failed', boot a replacement engine onto the same "
                   "device in the background (from --aot_cache_dir when "
                   "warm), validate its weights, and swap it in under "
                   "breaker probation (requires --replicas >= 2)")
    p.add_argument("--stream", action="store_true",
                   help="enable video stream sessions: POST bodies with a "
                   "\"stream_id\" carry the previous frame's disparity and "
                   "warm-start refinement (the flow_init prelude variants "
                   "are additionally warmed at boot)")
    p.add_argument("--stream_warm_iters", type=int, default=8,
                   help="refinement budget for warm-started stream frames "
                   "(cold frames use --max_iters)")
    p.add_argument("--stream_reset_ratio", type=float, default=2.5,
                   help="scene-cut gate: reset the session when the carried "
                   "flow's warp error on the new frame exceeds this ratio x "
                   "the error it achieved on its own frame")
    p.add_argument("--stream_reset_floor", type=float, default=4.0,
                   help="absolute warp-error floor (mean |I1-warp(I2)| in "
                   "[0,255] units) below which the gate never resets")
    p.add_argument("--max_streams", type=int, default=1024,
                   help="live stream-session ceiling (LRU eviction beyond it)")
    p.add_argument("--breaker_degrade_after", type=int, default=2,
                   help="consecutive batch failures before the health state "
                   "drops to 'degraded' (still admitting — probation traffic "
                   "is the recovery path)")
    p.add_argument("--breaker_fail_after", type=int, default=5,
                   help="consecutive batch failures that trip the breaker to "
                   "'failed': submits shed with 503 until a checkpoint swap "
                   "or restart")
    p.add_argument("--breaker_probation", type=int, default=2,
                   help="consecutive successes a degraded service needs to "
                   "read 'healthy' again")
    p.add_argument("--hang_timeout_s", type=float, default=0.0,
                   help="per-batch hang watchdog: a chunk with no heartbeat "
                   "for this long dumps all stacks and marks the service "
                   "'failed' (0 disables; size it to several times the "
                   "largest warmed chunk estimate)")
    p.add_argument("--drain_timeout_s", type=float, default=30.0,
                   help="graceful-shutdown budget: how long drain waits for "
                   "queued + in-flight requests before closing anyway")
    p.add_argument("--log_dir", default=None,
                   help="directory for serving diagnostics: breaker trips, "
                   "watchdog fires, and shutdown dump the flight recorder "
                   "(last-N request spans) as <log_dir>/flight_recorder.json "
                   "(unset = no dumps; tracing still runs in memory)")
    p.add_argument("--flight_recorder_events", type=int, default=512,
                   help="flight-recorder ring capacity for request lifecycle "
                   "spans (admission/queue/stage/chunk/finalize/respond; "
                   "0 disables recording)")
    p.add_argument("--reload_ckpt", default=None, metavar="PATH",
                   help="client mode: POST {\"checkpoint\": PATH} to "
                   "http://HOST:PORT/reload on an ALREADY-RUNNING server "
                   "(zero-recompile hot-swap), print the response, and exit "
                   "— no service is booted")
    _add_model_args(p)
    args = p.parse_args(argv)

    if args.reload_ckpt is not None:
        return _reload_checkpoint_client(args.host, args.port, args.reload_ckpt)

    import json

    from raft_stereo_tpu.config import ServeConfig, VideoConfig
    from raft_stereo_tpu.serving.service import StereoService, serve_http
    from raft_stereo_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    try:
        buckets = tuple(
            tuple(int(d) for d in b.lower().split("x")) for b in args.buckets
        )
    except ValueError:
        print(f"--buckets must look like 384x512, got {args.buckets}", file=sys.stderr)
        return 2
    if args.replicas == 0:
        # One replica per visible device — resolved here, not in the
        # config, so ServeConfig stays an honest record of the deployment.
        import jax

        args.replicas = len(jax.local_devices())
    video = None
    if args.stream:
        video = VideoConfig(
            chunk_iters=args.chunk_iters,
            cold_iters=args.max_iters,
            warm_iters=min(args.stream_warm_iters, args.max_iters),
            reset_error_ratio=args.stream_reset_ratio,
            reset_error_floor=args.stream_reset_floor,
        )
    config = ServeConfig(
        model=_model_config(args),
        buckets=buckets,
        max_batch=args.max_batch,
        chunk_iters=args.chunk_iters,
        max_iters=args.max_iters,
        deadline_ms=args.deadline_ms,
        batch_window_ms=args.batch_window_ms,
        replicas=args.replicas,
        host=args.host,
        port=args.port,
        restore_ckpt=args.restore_ckpt,
        sharding_rules=args.sharding_rules,
        video=video,
        max_streams=args.max_streams,
        breaker_degrade_after=args.breaker_degrade_after,
        breaker_fail_after=args.breaker_fail_after,
        breaker_probation=args.breaker_probation,
        hang_timeout_s=args.hang_timeout_s,
        drain_timeout_s=args.drain_timeout_s,
        log_dir=args.log_dir,
        flight_recorder_events=args.flight_recorder_events,
        aot_cache_dir=args.aot_cache_dir,
        auto_respawn=args.auto_respawn,
        hlo_audit=args.audit,
    )
    if args.require_cache_hit and not args.warmup_only:
        print("--require_cache_hit only makes sense with --warmup_only",
              file=sys.stderr)
        return 2
    variables = _load_variables(args.restore_ckpt, config.model)
    service = StereoService(config, variables).start()
    boot = service.boot_block()
    payload = {"warmup": service.warm_summary, "boot": boot}
    audit_block = None
    if args.audit:
        audit_block = service.hlo_audit_block()
        payload["hlo_audit"] = audit_block
    print(json.dumps(payload, default=str))
    if args.warmup_only:
        service.close()
        if audit_block is not None and audit_block.get("violations"):
            for detail in audit_block.get("violation_details", []):
                print(f"hlo audit: {detail.get('contract')} "
                      f"{detail.get('entry')}: {detail.get('message')}",
                      file=sys.stderr)
            print(f"--audit: {audit_block['violations']} contract "
                  "violation(s) in the warmed executables", file=sys.stderr)
            return 4
        if args.require_cache_hit:
            if not boot.get("cache_enabled"):
                print("--require_cache_hit: AOT cache is disabled "
                      "(missing or unwritable --aot_cache_dir)",
                      file=sys.stderr)
                return 3
            if int(boot.get("cache_misses", 0)) > 0:
                print(f"--require_cache_hit: {boot['cache_misses']} warmup "
                      f"entr{'y' if boot['cache_misses'] == 1 else 'ies'} "
                      "missed the AOT cache (compiled from scratch)",
                      file=sys.stderr)
                return 3
        return 0
    serve_http(service, config.host, config.port)
    return 0


def cmd_frontier(argv: List[str]) -> int:
    """Front-tier router (serving/frontier.py): route /predict across N
    backend `serve` hosts with health-checked breakers, retry/hedging,
    stream affinity and overload brownout. Holds no model — boots in
    milliseconds and never imports jax."""
    p = argparse.ArgumentParser(prog="frontier")
    p.add_argument("--backends", nargs="+", default=None, metavar="HOST:PORT",
                   help="backend StereoService addresses; routing prefers "
                   "healthy backends with the fewest in-flight forwards "
                   "(required in server mode; unused with --rollout)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8081)
    p.add_argument("--health_interval_s", type=float, default=2.0,
                   help="active /healthz probe interval; probe failures "
                   "feed the per-backend breaker, a probe success is the "
                   "only way a sticky-failed backend re-enters (probation)")
    p.add_argument("--health_timeout_s", type=float, default=5.0)
    p.add_argument("--request_timeout_s", type=float, default=600.0,
                   help="per-forward read timeout (bounds a wedged "
                   "connection; deadline_ms stays the latency authority)")
    p.add_argument("--retry_attempts", type=int, default=3,
                   help="total tries per plain request; retries prefer a "
                   "DIFFERENT backend, with jittered exponential backoff")
    p.add_argument("--retry_budget_percent", type=float, default=20.0,
                   help="retries allowed while retries_total < "
                   "retry_budget_min + this%% of requests_total — the "
                   "anti-amplification cap")
    p.add_argument("--retry_budget_min", type=int, default=10)
    p.add_argument("--hedge", action="store_true",
                   help="tail-latency hedging: duplicate a pending plain "
                   "request onto a second backend after max(live "
                   "queue-wait p95, --hedge_floor_ms) and take the first "
                   "answer")
    p.add_argument("--hedge_floor_ms", type=float, default=50.0)
    p.add_argument("--brownout_queue_p95_ms", type=float, default=0.0,
                   help="overload brownout threshold on the worst backend "
                   "queue-wait p95 (0 disables): above it, forwarded "
                   "deadlines/iters tighten so anytime engines early-exit "
                   "— quality degrades before anything is shed")
    p.add_argument("--brownout_deadline_ms", type=float, default=0.0,
                   help="deadline_ms clamp applied while browned out")
    p.add_argument("--brownout_max_iters", type=int, default=0,
                   help="max_iters cap applied while browned out")
    p.add_argument("--brownout_recover_ratio", type=float, default=0.5,
                   help="hysteresis: disengage only below threshold x this")
    p.add_argument("--breaker_degrade_after", type=int, default=1)
    p.add_argument("--breaker_fail_after", type=int, default=3)
    p.add_argument("--breaker_probation", type=int, default=2)
    p.add_argument("--drain_timeout_s", type=float, default=30.0)
    p.add_argument("--max_sessions", type=int, default=4096,
                   help="stream-session pinning table ceiling (LRU)")
    p.add_argument("--log_dir", default=None,
                   help="flight-recorder dumps land here as "
                   "frontier_flight_recorder.json (breaker moves, drain, "
                   "close)")
    p.add_argument("--flight_recorder_events", type=int, default=512)
    p.add_argument("--rollout", default=None, metavar="CKPT",
                   help="client mode: POST {\"checkpoint\": CKPT} to "
                   "http://HOST:PORT/rollout on an ALREADY-RUNNING "
                   "frontier — rolling fleet-wide reload with canary "
                   "verification and abort-rollback — print the rollout "
                   "record, and exit (no routing tier is booted)")
    p.add_argument("--rollback_ckpt", default=None, metavar="CKPT",
                   help="with --rollout: abort-rollback target for "
                   "backends that never reported a prior checkpoint path")
    p.add_argument("--force", action="store_true",
                   help="with --rollout: roll even when backend swap "
                   "generations already diverge (out-of-band reload)")
    p.add_argument("--rollout_stream_policy", choices=("migrate", "hold"),
                   default="migrate",
                   help="pinned stream sessions on a quiesced backend: "
                   "'migrate' cold-restarts them on another backend via "
                   "the generation-aliased affinity path; 'hold' parks "
                   "their frames until the host swaps back into rotation "
                   "(bounded by --rollout_hold_timeout_s, then migrates)")
    p.add_argument("--rollout_probation", type=int, default=2,
                   help="consecutive successful orchestrator probes a "
                   "swapped backend must pass before the roll proceeds")
    p.add_argument("--rollout_drain_timeout_s", type=float, default=30.0,
                   help="per-backend budget for in-flight forwards to "
                   "drain out before its reload (exceeding it aborts)")
    p.add_argument("--rollout_verify_timeout_s", type=float, default=30.0,
                   help="per-backend budget for the /healthz "
                   "swap_generation advance to become visible")
    p.add_argument("--rollout_hold_timeout_s", type=float, default=60.0,
                   help="how long requests park when the rollout flip "
                   "leaves no admissible backend, before shedding")
    args = p.parse_args(argv)

    if args.rollout is not None:
        return _rollout_client(
            args.host,
            args.port,
            args.rollout,
            args.rollback_ckpt,
            args.force,
        )
    if not args.backends:
        p.error("--backends is required (except with --rollout)")

    from raft_stereo_tpu.config import FrontierConfig
    from raft_stereo_tpu.serving.frontier import Frontier, serve_frontier_http

    config = FrontierConfig(
        backends=tuple(args.backends),
        host=args.host,
        port=args.port,
        health_interval_s=args.health_interval_s,
        health_timeout_s=args.health_timeout_s,
        request_timeout_s=args.request_timeout_s,
        retry_attempts=args.retry_attempts,
        retry_budget_percent=args.retry_budget_percent,
        retry_budget_min=args.retry_budget_min,
        hedge=args.hedge,
        hedge_floor_ms=args.hedge_floor_ms,
        brownout_queue_p95_ms=args.brownout_queue_p95_ms,
        brownout_deadline_ms=args.brownout_deadline_ms,
        brownout_max_iters=args.brownout_max_iters,
        brownout_recover_ratio=args.brownout_recover_ratio,
        breaker_degrade_after=args.breaker_degrade_after,
        breaker_fail_after=args.breaker_fail_after,
        breaker_probation=args.breaker_probation,
        drain_timeout_s=args.drain_timeout_s,
        max_sessions=args.max_sessions,
        rollout_stream_policy=args.rollout_stream_policy,
        rollout_probation=args.rollout_probation,
        rollout_drain_timeout_s=args.rollout_drain_timeout_s,
        rollout_verify_timeout_s=args.rollout_verify_timeout_s,
        rollout_hold_timeout_s=args.rollout_hold_timeout_s,
        log_dir=args.log_dir,
        flight_recorder_events=args.flight_recorder_events,
    )
    frontier = Frontier(config).start()
    serve_frontier_http(frontier, config.host, config.port)
    return 0


def cmd_demo(argv: List[str]) -> int:
    from raft_stereo_tpu.demo import add_demo_args, run_demo

    p = argparse.ArgumentParser(prog="demo")
    add_demo_args(p)
    _add_model_args(p)
    args = p.parse_args(argv)
    return run_demo(args, _model_config(args), _load_variables(args.restore_ckpt, _model_config(args)))


def main(argv: Optional[List[str]] = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)-8s [%(filename)s:%(lineno)d] %(message)s",
    )
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in ("train", "evaluate", "demo", "serve", "frontier"):
        print(
            "usage: python -m raft_stereo_tpu "
            "{train,evaluate,demo,serve,frontier} [args]",
            file=sys.stderr,
        )
        return 2
    return {
        "train": cmd_train,
        "evaluate": cmd_evaluate,
        "demo": cmd_demo,
        "serve": cmd_serve,
        "frontier": cmd_frontier,
    }[argv[0]](argv[1:])


if __name__ == "__main__":
    raise SystemExit(main())
