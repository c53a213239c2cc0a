"""Headline benchmark: Middlebury-F-resolution disparity maps per second at
32 GRU iterations (BASELINE.md north-star metric), measured on the attached
TPU with a synthetic full-resolution pair. It measures ONE configuration —
the one written below — and needs the chip: with no TPU it exits non-zero
before timing anything (a CPU number is never written under a device
metric's name).

Timing methodology: N forwards are chained inside ONE jitted scan (each
input perturbed by a scalar of the previous output, so the device must
execute them sequentially) and the host clock stops after
`block_until_ready` on the chain's result — free of per-call dispatch and
full-map device-to-host transfer overhead. Best of 3 trials.

The reference publishes no numeric FPS (BASELINE.md: "published": {}), so
`vs_baseline` is anchored to the first driver-recorded measurement of this
framework (0.7274 maps/s, 2026-07-30; ROADMAP "What the records are") — a
fixed, citable denominator that makes the field a round-over-round speedup
instead of echoing `value`.

Prints exactly one JSON line, which names the device it ran on. A section
that fails is named in that line and re-raised after it is printed, so the
exit code is non-zero.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# vs_baseline denominator: first driver-recorded measurement (see docstring).
_R01_BASELINE_MAPS_PER_SEC = 0.7274


def _hbm_estimate_gb(compiled):
    """Static XLA memory accounting for a compiled executable, in GB.

    Prefers `peak_memory_in_bytes` — the buffer assigner's liveness-aware
    peak, i.e. the HBM the executable actually reserves. The round-3 number
    summed temp+args+outputs−alias, which ignores liveness overlap and
    donation reuse and overcounted the b4 train step at 16.89 GB on a chip
    where the true assigned peak is 15.65 GB (round-3 verdict weak #4).
    Falls back to the naive sum when the field is absent/zero; None when the
    backend exposes no memory_analysis at all.

    Returns (gb, is_assigned_peak): callers must not HARD-fail on the naive
    sum (is_assigned_peak=False) — it is an upper bound that can exceed the
    true peak by >1 GB."""
    try:
        ma = compiled.memory_analysis()
        peak = getattr(ma, "peak_memory_in_bytes", 0)
        if peak:
            return peak / 1e9, True
        return (
            ma.temp_size_in_bytes
            + ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            - ma.alias_size_in_bytes
        ) / 1e9, False
    except Exception:
        return None, False


def _component_ms(fn, args, n=4, trials=3):
    """Per-execution milliseconds for `fn` chained n times inside one jit —
    the same serial-chain methodology as the headline (the first argument is
    perturbed by a scalar of the previous output, every output element feeds
    the carry so nothing dead-codes away)."""

    def chained(*a):
        def body(c, _):
            perturbed = (a[0] + (c * 1e-30).astype(a[0].dtype),) + a[1:]
            out = fn(*perturbed)
            tot = sum(jnp.sum(leaf.astype(jnp.float32)) for leaf in jax.tree.leaves(out))
            return tot * 1e-30, ()

        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
        return c

    cj = jax.jit(chained)
    jax.block_until_ready(cj(*args))  # compile + warmup
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(cj(*args))
        trial = (time.perf_counter() - t0) / n
        best = trial if best is None else min(best, trial)
    return best * 1e3


def main():
    import dataclasses

    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.models import RAFTStereo
    from raft_stereo_tpu.utils.compile_cache import setup_compile_cache
    from raft_stereo_tpu.utils.jit_hygiene import RecompileMonitor

    setup_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU path and found {devices[0].platform!r} "
            f"({devices[0].device_kind}); nothing was timed"
        )
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    # Sections that raised: each is named in the JSON line and the first is
    # re-raised after that line is printed (non-zero exit).
    failures = []

    # Compile accounting for the whole bench run (utils/jit_hygiene.py):
    # the expected compile population is fixed (chained hi/lo, init, train
    # steps, b2 forward, the component sub-timing chains), so a
    # round-over-round JUMP in `compiles_total` means something started
    # re-tracing — a perf regression
    # that per-metric timings can only show indirectly. Counting-only (no
    # grace protocol): advance() is never called.
    mon = RecompileMonitor(grace_steps=1, hard_fail=False, label="bench").start()

    # Middlebury 2014 full-res is ~2880x1988 (W x H); pad to /32 like the
    # reference eval (evaluate_stereo.py:162-163, InputPadder divis_by=32).
    h, w = 1984, 2880
    iters = 32
    # The configuration measured: the fused Pallas lookup over a bf16
    # pyramid, mixed precision, one image at a time through the encoder.
    # The undecided levers (fused_encoder, prefetch_lookup, fused_gru_tail;
    # ROADMAP D1) stay at their defaults, off — each gets its own chip A/B.
    cfg = RAFTStereoConfig(
        corr_implementation="pallas",
        mixed_precision=True,
        corr_dtype="bfloat16",
        sequential_encoder=True,
    )
    model = RAFTStereo(cfg)

    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    small = jnp.zeros((1, 64, 96, 3))
    variables = jax.jit(lambda r: model.init(r, small, small, iters=1))(jax.random.PRNGKey(0))

    n = 5

    def make_chained(m, chain_iters, chain_n):
        @jax.jit
        def chained(variables, image1, image2):
            def body(carry, _):
                # chain: next input depends on a scalar of the previous
                # output -> serial execution (1e-30: numerically negligible
                # but not constant-foldable)
                _, up = m.apply(
                    variables,
                    image1 + carry * 1e-30,
                    image2,
                    iters=chain_iters,
                    test_mode=True,
                )
                return up.reshape(-1)[0], ()
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=chain_n)
            return c
        return chained

    # Explicit lower/compile: the same executable serves timing AND the
    # static HBM accounting below (no second compile for memory analysis).
    chained = make_chained(model, iters, n).lower(variables, i1, i2).compile()

    def time_hi(fn):
        trials = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(variables, i1, i2))
            trials.append((time.perf_counter() - t0) / n)
        return trials

    jax.block_until_ready(chained(variables, i1, i2))  # warmup
    hi_trials = time_hi(chained)
    dt = min(hi_trials)

    maps_per_sec = 1.0 / dt

    # --- component breakdown: per-iteration slope from a second, shorter
    # iteration count (iters_lo); the intercept is the loop-invariant part
    # (encoders + corr state + upsample). Tracked in the bench JSON so
    # round-over-round regressions localize without re-profiling.
    # Interpretation caveat (measured, scripts/exp_chain_variance.py): the
    # within-session trial envelope is ±<1 ms, but identical configs drift
    # ±~25 ms (~2.8%) BETWEEN sessions (device state, compile schedule), so overhead
    # moves smaller than that across rounds are not decidable; the
    # per-iteration slope (21.6-21.7 ms every session) is the stable
    # regression signal.
    iters_lo = 8
    n_lo = 3
    chained_lo = make_chained(model, iters_lo, n_lo)
    jax.block_until_ready(chained_lo(variables, i1, i2))  # compile
    lo_trials = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chained_lo(variables, i1, i2))
        lo_trials.append((time.perf_counter() - t0) / n_lo)
    dt_lo = min(lo_trials)
    per_iter_ms = (dt - dt_lo) / (iters - iters_lo) * 1e3
    overhead_ms = (dt - per_iter_ms * 1e-3 * iters) * 1e3
    # Trial-spread envelope for the decomposition (round-4 review: an
    # ~18 ms overhead drift could hide in measurement noise unflagged —
    # the two-point split reuses both timings, so its error bars come from
    # evaluating the split over every (hi, lo) trial pairing).
    ov_all = []
    for th in hi_trials:
        for tl in lo_trials:
            s = (th - tl) / (iters - iters_lo)
            ov_all.append((th - s * iters) * 1e3)
    overhead_ms_range = (min(ov_all), max(ov_all))

    # --- per-component sub-timings of the loop-invariant overhead: the
    # encoders (fnet x2 + cnet, the dominant slice) and the corr-state
    # build, each timed in its own chained jit so kernel wins are
    # attributable per component; `fwd_other_ms` is the residual (context
    # heads, upsample, coords init, decomposition noise). Isolation
    # timings, not an exact partition — the residual absorbs the
    # difference, and the session-noise caveat above applies to all three.
    fwd_encoder_ms = fwd_corr_build_ms = None
    try:
        from raft_stereo_tpu.models.extractor import BasicEncoder, MultiBasicEncoder
        from raft_stereo_tpu.models.raft_stereo import _corr_state

        compute = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        fnet = BasicEncoder(
            output_dim=256, norm_fn="instance", downsample=cfg.n_downsample,
            fused_layer1=cfg.fused_encoder,
        )
        cnet = MultiBasicEncoder(
            output_dims=(tuple(cfg.hidden_dims), tuple(cfg.context_dims)),
            norm_fn="batch", downsample=cfg.n_downsample,
            fused_layer1=cfg.fused_encoder,
        )
        fvars = {"params": variables["params"]["fnet"]}
        cvars = {
            "params": variables["params"]["cnet"],
            "batch_stats": variables["batch_stats"]["cnet"],
        }

        def encoder_fwd(a, b):
            x1 = (2.0 * (a / 255.0) - 1.0).astype(compute)
            x2 = (2.0 * (b / 255.0) - 1.0).astype(compute)
            f1 = fnet.apply(fvars, x1)
            anchor = (f1.reshape(-1)[0] * 1e-30).astype(x2.dtype)
            f2 = fnet.apply(fvars, x2 + anchor)
            scales = cnet.apply(cvars, x1, num_layers=cfg.n_gru_layers)
            return f1, f2, scales

        fwd_encoder_ms = _component_ms(encoder_fwd, (i1, i2), n=3)

        # Synthetic fmaps: the corr build is value-independent, so this
        # skips a second full-res encoder compile.
        fs = (1, h // cfg.downsample_factor, w // cfg.downsample_factor, 256)
        frng = np.random.default_rng(1)
        fm1 = jnp.asarray(frng.standard_normal(fs).astype(np.float32)).astype(compute)
        fm2 = jnp.asarray(frng.standard_normal(fs).astype(np.float32)).astype(compute)
        fwd_corr_build_ms = _component_ms(
            lambda a, b: _corr_state(cfg, a, b, fused=cfg.fused_encoder),
            (fm1, fm2), n=6,
        )
    except Exception as e:
        failures.append(e)
        sub_timing_error = f"{type(e).__name__}: {e}"[:200]
    else:
        sub_timing_error = None

    # --- per-iteration fast path: attribution + lever A/Bs. The two-point
    # slope above says WHAT an iteration costs; this block says WHERE —
    # corr lookup vs GRU update block vs residual — with the residual
    # constructed so the three sub-timings partition `fwd_per_iter_ms`
    # EXACTLY (the fwd_overhead_ms sum-check discipline, enforced by
    # check_bench_json validate_per_iter). Each fast-path lever (bf16 corr
    # volume, scalar-prefetch lookup, fused GRU tail) gets its own on/off
    # component A/B so BENCH_r06 settles each verdict independently. The
    # `memory` block reads the obs/memory.py allocator telemetry with a
    # bytes_in_use delta across the corr-state build — the MEASURED
    # corr-pyramid footprint that replaces BENCH_r05's 5.41 GB estimate.
    per_iter_block = memory_blk = corr_precision_blk = None
    fast_path_error = None
    try:
        from raft_stereo_tpu.data.datasets import make_synthetic_sequence
        from raft_stereo_tpu.models.raft_stereo import _corr_state
        from raft_stereo_tpu.models.update import BasicMultiUpdateBlock
        from raft_stereo_tpu.obs.memory import memory_block
        from raft_stereo_tpu.ops.corr import BF16_CORR_EPE_BUDGET_PX, corr_lookup

        compute2 = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        fh, fw = h // cfg.downsample_factor, w // cfg.downsample_factor
        prng = np.random.default_rng(2)
        pm1 = jnp.asarray(prng.standard_normal((1, fh, fw, 256)).astype(np.float32)).astype(compute2)
        pm2 = jnp.asarray(prng.standard_normal((1, fh, fw, 256)).astype(np.float32)).astype(compute2)

        # Measured corr-pyramid HBM: allocator bytes_in_use delta across the
        # state build, sampled while HOLDING the built state (so the delta is
        # the state's resident footprint, temps freed). available=false (CPU)
        # degrades to 0 — validate_memory's contract.
        pre_mem = memory_block()
        # Eager build (op-by-op, no jit): the delta wants the HELD state's
        # resident bytes, not a compiled program's temp schedule.
        corr_state_live = _corr_state(cfg, pm1, pm2, fused=cfg.fused_encoder)
        jax.block_until_ready(corr_state_live)
        post_mem = memory_block()
        memory_blk = dict(post_mem)
        memory_blk["corr_pyramid_bytes"] = (
            max(0, post_mem["bytes_in_use"] - pre_mem["bytes_in_use"])
            if post_mem["available"]
            else 0
        )

        # Plausible lookup coordinates: the pixel grid minus a smooth bounded
        # disparity — the regime the model produces, and the one where the
        # prefetch kernel's windows fit (its fits-predicate falls back to the
        # dense kernel otherwise, which would make the A/B measure nothing).
        xs = np.broadcast_to(np.arange(fw, dtype=np.float32), (1, fh, fw))
        dsp = 30.0 * (0.5 + 0.5 * np.sin(np.linspace(0.0, 4.0, fw, dtype=np.float32)))
        coords = jnp.asarray(xs - dsp[None, None, :])

        radius = cfg.corr_radius
        if cfg.corr_implementation == "pallas":
            from raft_stereo_tpu.ops.corr_pallas import (
                pallas_corr_lookup_padded,
                prefetch_corr_lookup_padded,
            )

            def lookup_fn(c, s):
                return pallas_corr_lookup_padded(s, c, radius, compute2)
        else:

            def lookup_fn(c, s):
                return corr_lookup(s, c, radius)

        iter_corr_lookup_ms = _component_ms(lookup_fn, (coords, corr_state_live), n=8)

        # Update-block component: synthetic per-scale hidden states + context
        # biases at the model's own shapes, params from the real tree.
        ub_kwargs = dict(
            hidden_dims=tuple(cfg.hidden_dims),
            corr_channels=cfg.corr_channels,
            n_gru_layers=cfg.n_gru_layers,
            n_downsample=cfg.n_downsample,
        )
        ub = BasicMultiUpdateBlock(**ub_kwargs)
        ub_vars = {"params": variables["params"]["iteration"]["update_block"]}
        net, ctx = [], []
        for i in range(cfg.n_gru_layers):
            sh, sw, width = fh >> i, fw >> i, cfg.hidden_dims[2 - i]
            net.append(
                jnp.asarray(prng.standard_normal((1, sh, sw, width)).astype(np.float32)).astype(compute2)
            )
            ctx.append(tuple(
                jnp.asarray(prng.standard_normal((1, sh, sw, width)).astype(np.float32)).astype(compute2)
                for _ in range(3)
            ))
        net, ctx = tuple(net), tuple(ctx)
        corr_taps = jnp.asarray(
            prng.standard_normal((1, fh, fw, cfg.corr_channels)).astype(np.float32)
        ).astype(compute2)
        flow_in = jnp.asarray(prng.standard_normal((1, fh, fw, 1)).astype(np.float32)).astype(compute2)

        def gru_fn_for(module):
            def fn(c):
                return module.apply(
                    ub_vars, net, ctx, c, flow_in,
                    iter32=cfg.n_gru_layers == 3,
                    iter16=cfg.n_gru_layers >= 2,
                )
            return fn

        iter_gru_ms = _component_ms(gru_fn_for(ub), (corr_taps,), n=6)

        per_iter_block = {
            # Residual from the UNROUNDED components, so the three rounded
            # sub-timings sum to fwd_per_iter_ms within rounding slack — the
            # exact-partition contract validate_per_iter enforces. The
            # residual is signed: the isolation timings can overshoot the
            # two-point slope (session-noise caveat above).
            "iter_corr_lookup_ms": round(iter_corr_lookup_ms, 3),
            "iter_gru_ms": round(iter_gru_ms, 3),
            "iter_other_ms": round(per_iter_ms - iter_corr_lookup_ms - iter_gru_ms, 3),
        }

        levers = {}
        # bf16 corr volume: the SAME lookup against the other-dtype state
        # (the build-cost side of the lever rides fwd_corr_build_ms; the
        # per-iteration side — halved gather traffic — is what this times).
        alt_dtype = "float32" if cfg.corr_dtype == "bfloat16" else "bfloat16"
        state_alt = _corr_state(
            dataclasses.replace(cfg, corr_dtype=alt_dtype), pm1, pm2,
            fused=cfg.fused_encoder,
        )
        jax.block_until_ready(state_alt)
        ms_alt = _component_ms(lookup_fn, (coords, state_alt), n=8)
        if cfg.corr_dtype == "bfloat16":
            levers["corr_bf16"] = {"on_ms": round(iter_corr_lookup_ms, 3), "off_ms": round(ms_alt, 3)}
        else:
            levers["corr_bf16"] = {"on_ms": round(ms_alt, 3), "off_ms": round(iter_corr_lookup_ms, 3)}
        del state_alt

        if cfg.corr_implementation == "pallas":
            # Scalar-prefetch windowed lookup vs the dense kernel, same state.
            def pf_fn(c, s):
                return prefetch_corr_lookup_padded(s, c, radius, compute2)

            ms_pf = _component_ms(pf_fn, (coords, corr_state_live), n=8)
            levers["prefetch_lookup"] = {
                "on_ms": round(ms_pf, 3),
                "off_ms": round(iter_corr_lookup_ms, 3),
            }
        # Fused GRU tail + motion concat vs the XLA formulation.
        ub_ft = BasicMultiUpdateBlock(**ub_kwargs, fused_tail=True)
        ms_ft = _component_ms(gru_fn_for(ub_ft), (corr_taps,), n=6)
        levers["fused_gru_tail"] = {
            "on_ms": round(ms_ft, 3),
            "off_ms": round(iter_gru_ms, 3),
        }
        per_iter_block["levers"] = levers
        del corr_state_live

        # bf16-corr accuracy cost on a synthetic eval with known disparity:
        # EPE under an fp32 vs a bf16 pyramid, same weights, same input —
        # the delta is gated against the declared budget by check_bench_json
        # (the constant is pinned to ops.corr.BF16_CORR_EPE_BUDGET_PX by a
        # tier-1 test). TWO iterations, fp32 compute: at random init the
        # GRU is not contractive, so pyramid rounding amplifies chaotically
        # with iteration count (measured: delta 0.012 px at 2 iters vs
        # 6.1 px at 16) — the 2-iter fp32-compute delta is the bounded,
        # lever-isolated quantity the budget governs. Re-anchor at 32 iters
        # when a trained (contractive) checkpoint lands (ROADMAP item 4).
        eh, ew = 384, 512
        frame = make_synthetic_sequence(np.random.default_rng(5), 1, eh, ew)[0]
        e1 = jnp.asarray(frame["image1"][None])
        e2 = jnp.asarray(frame["image2"][None])
        gt = jnp.asarray(frame["flow"])
        evalid = jnp.asarray(frame["valid"])

        def epe_for(dt):
            mp = RAFTStereo(
                dataclasses.replace(cfg, corr_dtype=dt, mixed_precision=False)
            )
            _, up = jax.jit(
                lambda v, a, b: mp.apply(v, a, b, iters=2, test_mode=True)
            )(variables, e1, e2)
            err = jnp.abs(up[0, :, :, 0] - gt[..., 0])
            return float(jnp.sum(err * evalid) / jnp.sum(evalid))

        epe_fp32 = epe_for("float32")
        epe_bf16 = epe_for("bfloat16")
        corr_precision_blk = {
            "corr_dtype": cfg.corr_dtype,
            "epe_fp32": round(epe_fp32, 4),
            "epe_bf16": round(epe_bf16, 4),
            "epe_delta_px": round(abs(epe_bf16 - epe_fp32), 4),
            "epe_budget_px": BF16_CORR_EPE_BUDGET_PX,
            "eval": "synthetic 384x512 known-disparity pair, 2 iters, fp32 compute",
        }
    except Exception as e:
        failures.append(e)
        fast_path_error = f"{type(e).__name__}: {e}"[:200]

    # --- peak HBM guard (round-1 advisor): full-res inference must stay
    # well inside one v5e chip; an XLA fusion regression that materializes
    # fp32 full-res copies shows up here before it shows up as an OOM.
    peak_hbm_gb = devices[0].memory_stats()["peak_bytes_in_use"] / 1e9
    # Beside it, XLA's compile-time accounting for the already-built
    # chained-forward executable (the scan reuses buffers across chain
    # steps, so this tracks the single forward's footprint): it moves with
    # fusion regressions even where the allocator's peak is set elsewhere.
    hbm_est_fwd_gb, _ = _hbm_estimate_gb(chained)

    # --- training step at the reference recipe (README.md:109-113): batch 4
    # per chip, 320x720 crops, 22 iterations, bf16 — steps/sec/chip is a
    # BASELINE.md tracked metric. Guarded: a failure here (e.g. HBM
    # regression) must not discard the already-measured forward numbers.
    result = {
        "metric": "middlebury_F_maps_per_sec_32iters",
        "device": device,
        "value": round(maps_per_sec, 4),
        "unit": "maps/s",
        "vs_baseline": round(maps_per_sec / _R01_BASELINE_MAPS_PER_SEC, 4),
        "fwd_per_iter_ms": round(per_iter_ms, 3),
        "fwd_overhead_ms": round(overhead_ms, 1),
        # Envelope over all (hi, lo) trial pairings — if round-over-round
        # overhead numbers overlap within these ranges, the movement is
        # measurement noise, not a regression (round-4 review).
        "fwd_overhead_ms_range": [round(overhead_ms_range[0], 1), round(overhead_ms_range[1], 1)],
        "fwd_trials_s": [round(t, 4) for t in hi_trials],
        # Roofline context (round-3 trace, ROADMAP "Where the remaining
        # forward time is"): per-iteration conv FLOPs execute at >=80% MXU;
        # the floor without architectural change is ~13 ms/iter.
        "fwd_per_iter_floor_ms": 13.0,
    }
    # Per-component overhead attribution (see measurement note above).
    if fwd_encoder_ms is not None and fwd_corr_build_ms is not None:
        result["fwd_encoder_ms"] = round(fwd_encoder_ms, 1)
        result["fwd_corr_build_ms"] = round(fwd_corr_build_ms, 1)
        result["fwd_other_ms"] = round(
            overhead_ms - fwd_encoder_ms - fwd_corr_build_ms, 1
        )
    elif sub_timing_error is not None:
        result["sub_timing_error"] = sub_timing_error
    # Per-iteration fast-path attribution + lever A/Bs, measured corr-pyramid
    # footprint, and the bf16-corr accuracy gate (see block above).
    if per_iter_block is not None:
        result["per_iter"] = per_iter_block
    if memory_blk is not None:
        result["memory"] = memory_blk
    if corr_precision_blk is not None:
        result["corr_precision"] = corr_precision_blk
    if fast_path_error is not None:
        result["fast_path_error"] = fast_path_error
    result["fused_encoder_used"] = bool(cfg.fused_encoder)
    try:
        train, train_hbm = _train_step_seconds(batch=4)
        result["train_step_s"] = round(train, 4)
        result["steps_per_sec_chip"] = round(1.0 / train, 4)
        if train_hbm is not None:
            result["hbm_est_train_gb"] = round(train_hbm, 2)
    except Exception as e:  # still print the forward metrics
        failures.append(e)
        result["train_step_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        # Reference-recipe north star (BASELINE.md): 200k steps at GLOBAL
        # batch 8 in <24 h on v5e-64. Global batch 8 shards over the tested
        # DP mesh; batch 1/chip on 8 chips is the fastest measured layout
        # (gradient all-reduce of ~11M params over ICI is sub-ms).
        # `_extrapolated` suffix (round-3 verdict weak #5): the 8-chip wall
        # clock is MODELED from the measured single-chip step time + a
        # sub-ms ICI all-reduce assumption — this rig has one chip, so the
        # multi-chip number cannot be measured here (sharding correctness
        # is separately proven by the dryrun + mesh tests).
        # Best of two fresh compiles: the b1 step varies ±~2.5% across
        # compiles of the same code (round-5 measurements 0.1542-0.1584 in
        # one session) — compile-schedule lottery, not trial noise — and
        # this field sets the recipe-hours headline.
        b1_trials = [_train_step_seconds(batch=1)[0] for _ in range(2)]
        train_b1 = min(b1_trials)
        result["train_step_s_b1"] = round(train_b1, 4)
        result["train_step_s_b1_trials"] = [round(t, 4) for t in b1_trials]
        result["recipe_200k_hours_8chip_dp_extrapolated"] = round(200_000 * train_b1 / 3600, 2)
    except Exception as e:
        failures.append(e)
        result["train_step_b1_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        # Batched inference (round-3 verdict weak #2): B=2 as a scan of
        # single-pair forwards (models.sequential_batch_forward — nothing
        # in this model is shared across batch elements, so per-map parity
        # with B=1 is the single-chip physical ceiling; the old scan-form
        # encoder paid a ~5.6% penalty below it). Memory stays flat at the
        # B=1 footprint for any batch.
        from raft_stereo_tpu.models import sequential_batch_forward

        b2 = 2
        i1b = jnp.concatenate([i1, i2], axis=0)
        i2b = jnp.concatenate([i2, i1], axis=0)

        @jax.jit
        def b2_fwd(variables, a, b):
            def chain_body(carry, _):
                _, up = sequential_batch_forward(
                    model, variables, a + carry * 1e-30, b, iters=iters
                )
                return up.reshape(-1)[0], ()
            c, _ = jax.lax.scan(chain_body, jnp.float32(0), None, length=2)
            return c

        jax.block_until_ready(b2_fwd(variables, i1b, i2b))  # compile
        # Best-of-3 like the headline (round-4 review weak #4: best-of-2
        # recorded 1.0695 vs 1.0739 — under parity — while reruns showed
        # overlapping ranges; the committed JSON must carry the evidence).
        b2_trials = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(b2_fwd(variables, i1b, i2b))
            b2_trials.append((time.perf_counter() - t0) / 2)
        result["b2_maps_per_sec"] = round(b2 / min(b2_trials), 4)
        result["b2_maps_per_sec_trials"] = [round(b2 / t, 4) for t in b2_trials]

        # Batch-scaling sweep (PR-7 satellite): b1/b2/b4 per-map throughput
        # as a trajectory, so batching-efficiency changes show up round over
        # round instead of as a one-off b2 claim. b1 is the headline number;
        # b2/b4 ride the same sequential_batch_forward construction (memory
        # stays flat at the B=1 footprint — a true batched full-res forward
        # OOMs the chip, which is WHY per-map cost is structurally
        # B-independent on a single chip at full resolution: nothing is
        # shared across batch elements. The serving tier's bucket-shaped
        # batches are where real amortization lives; bench_serving.py's
        # batch_efficiency A/B measures it).
        sweep = {"b1": result["value"]}
        if "b2_maps_per_sec" in result:
            sweep["b2"] = result["b2_maps_per_sec"]
        for bsz in (4,):
            ib1 = jnp.concatenate([i1, i2] * (bsz // 2), axis=0)
            ib2 = jnp.concatenate([i2, i1] * (bsz // 2), axis=0)

            @jax.jit
            def bn_fwd(variables, a, b):
                _, up = sequential_batch_forward(model, variables, a, b, iters=iters)
                return up.reshape(-1)[0]

            jax.block_until_ready(bn_fwd(variables, ib1, ib2))  # compile
            bn_trials = []
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(bn_fwd(variables, ib1, ib2))
                bn_trials.append((time.perf_counter() - t0) / bsz)
            sweep[f"b{bsz}"] = round(1.0 / min(bn_trials), 4)
        result["batch_scaling"] = sweep
        result["batch_scaling_mode"] = (
            "sequential_batch_forward (memory-flat scan of single-pair "
            "forwards; per-map parity with b1 is the single-chip ceiling "
            "at full res — see bench_serving.py batch_efficiency for "
            "bucket-shape amortization)"
        )
    except Exception as e:
        failures.append(e)
        result["b2_error"] = f"{type(e).__name__}: {e}"[:200]

    try:
        # Streaming/video stereo (PR-10): steady-state maps/s of a warm-
        # started StreamSession plus the warm-vs-cold iters_to_epe_parity
        # A/B, on a moderate-resolution synthetic drifting-disparity
        # sequence (full-res video at 32 cold iters would dominate the
        # bench's wall clock without changing the verdict — the warm-start
        # win is resolution-independent). Adds one session compile set +
        # one parity compile set to compiles_total — a one-time step up in
        # the round this landed, like the r06 sub-timing chains.
        from raft_stereo_tpu.config import VideoConfig
        from raft_stereo_tpu.data.datasets import make_synthetic_sequence
        from raft_stereo_tpu.video import (
            StreamSession,
            replay_sequence,
            warm_cold_parity,
        )

        vh, vw = 704, 1280
        video_cfg = VideoConfig(chunk_iters=8, cold_iters=32, warm_iters=8)
        vframes = make_synthetic_sequence(np.random.default_rng(10), 6, vh, vw)
        session = StreamSession(cfg, variables, video_cfg)
        replay = replay_sequence(session, vframes)
        parity = warm_cold_parity(cfg, variables, vframes[:3], video_cfg)
        result["video"] = {
            "video_maps_per_sec": round(replay["video_maps_per_sec"], 4),
            "frames": replay["frames"],
            "warm_frames": replay["warm_frames"],
            "resets": replay["resets"],
            "resolution": [vh, vw],
            "warm_iters": video_cfg.warm_iters,
            "cold_iters": video_cfg.cold_iters,
            "iters_to_epe_parity": parity,
        }
    except Exception as e:
        failures.append(e)
        result["video_error"] = f"{type(e).__name__}: {e}"[:200]

    try:
        # HLO contract audit (tools/graftaudit): compile + snapshot the slim
        # eval forward and run the GA contract table over it, so the bench
        # record carries a per-round contract verdict (the serving-side
        # warm-set audit rides in bench_serving.py's hlo_audit block). Slim
        # on purpose: the contracts are wiring claims, and auditing the
        # full-width forward here would double this bench's compile bill.
        # Adds one compile set to compiles_total in the round this landed.
        from tools.graftaudit.contracts import audit_records as _audit_records
        from tools.graftaudit.live import eval_record as _eval_record

        _violations, _stats = _audit_records([_eval_record(preset="dp")])
        result["hlo_audit"] = dict(
            _stats,
            violation_details=[v.as_dict() for v in _violations],
        )
    except Exception as e:
        failures.append(e)
        result["hlo_audit_error"] = f"{type(e).__name__}: {e}"[:200]
    # North-star frame (round-3 verdict weak #7): BASELINE.md's target is
    # >=4x RTX-6000 inference throughput on v5e-8 at iso-EPE. The v5e-8
    # number below is the single-chip measurement x8 (Middlebury-F maps are
    # independent; batch-parallel scaling over 8 chips has no cross-chip
    # traffic) — extrapolated, not measured, on this 1-chip rig. No public
    # RTX-6000 maps/s figure exists for the reference (BASELINE.md
    # "published": {}), so the absolute comparison waits for the first
    # networked/multi-chip environment; README "Benchmarks" records this.
    result["v5e8_maps_per_sec_extrapolated"] = round(8 * maps_per_sec, 2)
    hbm_limit_gb = 14.0  # measured-runtime-peak guard for a 16 GB v5e chip
    result["peak_hbm_gb"] = round(peak_hbm_gb, 2)
    if hbm_est_fwd_gb is not None:
        result["hbm_est_fwd_gb"] = round(hbm_est_fwd_gb, 2)
    # Train-step guard (round-3 verdict weak #4): the b4 recipe step must
    # keep fitting one chip; a regression shows up here before it OOMs a
    # real training run. Anchor: the step demonstrably runs at 15.65 GB
    # assigned peak on the 16 GB chip, so the warn line sits just above the
    # healthy value — any warn means NEW allocations landed in the step.
    train_warn_gb = 15.75
    train_gb = result.get("hbm_est_train_gb")
    if train_gb is not None and train_gb >= train_warn_gb:
        result["hbm_train_warn"] = (
            f"static train peak {train_gb:.2f} GB >= {train_warn_gb} GB "
            "(healthy anchor 15.65) — review before the b4 recipe OOMs"
        )
    # Recompile accounting (PR-4 ROADMAP open item): the total backend
    # compiles this bench run triggered, for round-over-round comparison.
    result["compiles_total"] = mon.stats()["compiles_total"]
    # Always print the JSON line first (the driver records it), THEN fail —
    # aborting before printing would discard the round's measurements
    # exactly when they matter most.
    print(json.dumps(result))
    if failures:
        raise failures[0]
    if peak_hbm_gb >= hbm_limit_gb:
        raise RuntimeError(
            f"full-res inference peak HBM {peak_hbm_gb:.1f} GB leaves no "
            f"headroom against the {hbm_limit_gb:.0f} GB v5e guard — "
            "fusion regression?"
        )


def _train_step_seconds(batch: int = 4):
    """(seconds/step, static HBM estimate GB) at the reference recipe on
    this chip (train_iters 22, 320x720 crops, bf16, Pallas corr, full
    backward + optimizer update) at the given per-chip batch size."""
    from raft_stereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raft_stereo_tpu.parallel.mesh import shard_batch
    from raft_stereo_tpu.train.trainer import Trainer

    cfg = TrainConfig(
        model=RAFTStereoConfig(
            corr_implementation="pallas",
            mixed_precision=True,
            corr_dtype="bfloat16",
        ),
        batch_size=batch,
        train_iters=22,
        mesh_shape=(1, 1),
        num_steps=10**6,
    )
    trainer = Trainer(cfg, sample_shape=(320, 720, 3))
    rng = np.random.default_rng(0)
    data = shard_batch(trainer.mesh, {
        "image1": rng.uniform(0, 255, (batch, 320, 720, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (batch, 320, 720, 3)).astype(np.float32),
        "flow": rng.uniform(-40, 0, (batch, 320, 720, 1)).astype(np.float32),
        "valid": np.ones((batch, 320, 720), np.float32),
    })

    # One explicit compile serves both the static memory accounting and the
    # timed calls (donation is baked into the executable).
    step = trainer.train_step.lower(trainer.state, data).compile()
    hbm_gb, _ = _hbm_estimate_gb(step)

    state = trainer.state
    state, metrics = step(state, data)  # warmup
    jax.block_until_ready(metrics["epe"])

    n = 8
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(n):
            # back-to-back async dispatch; the donated state chains the steps
            state, metrics = step(state, data)
        jax.block_until_ready(metrics["epe"])  # one sync for the whole chain
        trial = (time.perf_counter() - t0) / n
        best = trial if best is None else min(best, trial)
    return best, hbm_gb


if __name__ == "__main__":
    main()
