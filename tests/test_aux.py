"""Aux subsystems: the profile capture, multi-host init, CLI surface."""

import os

import jax
import jax.numpy as jnp
import pytest


def test_trace_writes_profile(tmp_path):
    from raft_stereo_tpu.obs import profile

    logdir = str(tmp_path / "prof")
    with profile(logdir):
        jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    found = [
        os.path.join(r, f)
        for r, _, files in os.walk(logdir)
        for f in files
        if f.endswith((".trace.json.gz", ".xplane.pb"))
    ]
    assert found, f"no trace artifacts under {logdir}"


def test_init_multihost_single_process_noop():
    from raft_stereo_tpu.parallel.distributed import host_shard_args, init_multihost

    info = init_multihost()
    assert info["process_count"] == 1 and info["process_index"] == 0
    assert host_shard_args() == {"host_id": 0, "num_hosts": 1}


@pytest.mark.parametrize("sub", ["train", "evaluate", "demo"])
def test_cli_help(sub, capsys):
    from raft_stereo_tpu.cli import main

    with pytest.raises(SystemExit) as e:
        main([sub, "--help"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    assert "--corr_implementation" in out


# --- bench.py helpers (driver-critical: these decide whether a round's
# numbers are recorded or the bench hard-fails; both paths were reshaped by
# advisor findings in rounds 3-4 and deserve direct coverage).


class _FakeMemoryAnalysis:
    def __init__(self, peak=0, temp=0, args=0, out=0, alias=0):
        self.peak_memory_in_bytes = peak
        self.temp_size_in_bytes = temp
        self.argument_size_in_bytes = args
        self.output_size_in_bytes = out
        self.alias_size_in_bytes = alias


class _FakeCompiled:
    def __init__(self, ma):
        self._ma = ma

    def memory_analysis(self):
        if isinstance(self._ma, Exception):
            raise self._ma
        return self._ma


def test_hbm_estimate_prefers_assigned_peak():
    import bench

    gb, is_peak = bench._hbm_estimate_gb(_FakeCompiled(_FakeMemoryAnalysis(peak=12_480_000_000)))
    assert is_peak and abs(gb - 12.48) < 1e-9


def test_hbm_estimate_naive_sum_fallback():
    import bench

    # peak absent/zero -> temp + args + out - alias, flagged as NOT a peak
    ma = _FakeMemoryAnalysis(peak=0, temp=10e9, args=4e9, out=2e9, alias=1e9)
    gb, is_peak = bench._hbm_estimate_gb(_FakeCompiled(ma))
    assert not is_peak and abs(gb - 15.0) < 1e-9


def test_hbm_estimate_no_backend_support():
    import bench

    gb, is_peak = bench._hbm_estimate_gb(_FakeCompiled(NotImplementedError("no stats")))
    assert gb is None and not is_peak


# --- scripts/check_bench_json.py (the round-JSON schema the driver and
# round-over-round comparisons key on) ------------------------------------

def _bench_validator():
    import sys

    scripts = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import check_bench_json

    return check_bench_json


def test_bench_schema_selftest_clean():
    assert _bench_validator()._selftest() == []


# The last record the driver took before this round (2026-08-02, one v5e
# chip; ROADMAP "What the records are" keeps the numbers as history), in the
# driver's wrapper form: the schema must keep accepting what bench.py
# printed then.
_R05_RECORD = {
    "n": 5,
    "rc": 0,
    "parsed": {
        "metric": "middlebury_F_maps_per_sec_32iters",
        "value": 1.0835,
        "unit": "maps/s",
        "vs_baseline": 1.4896,
        "fwd_per_iter_ms": 21.502,
        "fwd_overhead_ms": 234.8,
        "fwd_overhead_ms_range": [234.7, 235.3],
        "fwd_trials_s": [0.9229, 0.9234, 0.9231],
        "fwd_per_iter_floor_ms": 13.0,
        "train_step_s": 0.4252,
        "steps_per_sec_chip": 2.3521,
        "hbm_est_train_gb": 10.81,
        "train_step_s_b1": 0.1516,
        "train_step_s_b1_trials": [0.1516, 0.1519],
        "recipe_200k_hours_8chip_dp_extrapolated": 8.42,
        "b2_maps_per_sec": 1.073,
        "b2_maps_per_sec_trials": [1.0729, 1.0721, 1.073],
        "v5e8_maps_per_sec_extrapolated": 8.67,
        "hbm_est_fwd_gb": 5.41,
    },
}


def test_bench_schema_accepts_r05_record():
    cbj = _bench_validator()
    assert cbj.validate(cbj._extract(_R05_RECORD)) == []


def test_bench_schema_rejects_subtiming_drift():
    """The three sub-timings are a partition of fwd_overhead_ms by
    construction; a validator that tolerated drift would let the
    attribution silently diverge from the headline."""
    cbj = _bench_validator()
    rec = {
        "metric": "m", "value": 1.0, "unit": "maps/s", "vs_baseline": 1.0,
        "fwd_per_iter_ms": 20.0, "fwd_overhead_ms": 100.0,
        "fwd_overhead_ms_range": [99.0, 101.0], "fwd_trials_s": [0.8],
        "fwd_per_iter_floor_ms": 13.0,
        "fwd_encoder_ms": 70.0, "fwd_corr_build_ms": 10.0, "fwd_other_ms": 40.0,
    }
    errs = cbj.validate(rec)
    assert any("sub-timings sum" in e for e in errs)
    rec["fwd_other_ms"] = 20.0
    assert cbj.validate(rec) == []


def test_bench_schema_rejects_loser_headline():
    cbj = _bench_validator()
    rec = {
        "metric": "m", "value": 1.0, "unit": "maps/s", "vs_baseline": 1.0,
        "fwd_per_iter_ms": 20.0, "fwd_overhead_ms": 100.0,
        "fwd_overhead_ms_range": [99.0, 101.0], "fwd_trials_s": [0.8],
        "fwd_per_iter_floor_ms": 13.0,
        "fwd_total_fused_s": 0.9, "fwd_total_xla_s": 0.8,
        "fused_encoder_used": True,
    }
    errs = cbj.validate(rec)
    assert any("did not pick the winner" in e for e in errs)


# --- scripts/exp_compiler_options.py --config validation ------------------

def test_exp_compiler_options_config_specs_validate():
    """Malformed --config specs must die with a usage error NAMING the bad
    key/value (ROADMAP carried advisor low exp_compiler_options.py:140),
    never the opaque dict-comprehension ValueError."""
    import sys

    scripts = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from exp_compiler_options import parse_config_specs

    errors = []

    def error(msg):
        errors.append(msg)
        raise SystemExit(2)

    runs = parse_config_specs(["a=1,b=2", " c = 3 "], error)
    assert runs == [("a=1,b=2", {"a": "1", "b": "2"}), (" c = 3 ", {"c": "3"})]
    assert errors == []

    for bad, needle in [
        ("a=1,b", "missing '='"),
        ("=5", "empty option name"),
        ("a=", "empty value"),
        ("   ", "spec is empty"),
    ]:
        errors.clear()
        with pytest.raises(SystemExit):
            parse_config_specs([bad], error)
        assert errors and needle in errors[0], (bad, errors)
