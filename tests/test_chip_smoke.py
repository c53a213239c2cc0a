"""CPU rehearsal of `chip_smoke.py` (first of the three rehearsals before a
chip run): every phase end to end at a tiny size, through the same functions
the chip run calls — only `Sizes` differs. Here, and only here, the Pallas
kernels run interpreted. Also the device gate and the compile-cache helper.
"""

import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

# The tiny trainer shape of the verify notes (.claude/skills/verify/SKILL.md,
# "Crash-consistent resume surface"), with the smoke's kernels and dtypes.
TINY = chip_smoke.Sizes(
    model=dict(
        chip_smoke.Sizes().model,
        hidden_dims=(16, 16, 16),
        n_gru_layers=1,
        corr_levels=2,
        corr_radius=2,
    ),
    infer_hw=(64, 96),
    infer_iters=2,
    parity_hw=(64, 96),
    serve_image_hw=(60, 90),
    serve_bucket=(64, 96),
    chunk_iters=2,
    max_iters=4,
    batch_window_ms=1000.0,
    fleet_image_hw=(60, 90),
    train_batch=2,
    train_hw=(32, 48),
    train_iters=2,
)
SEED = 0


def _lines(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()]


def test_gate_refuses_a_cpu_with_a_clear_message(capsys):
    """The driver runs `python3 chip_smoke.py` in a sandbox first, where it
    must fail: no TPU, no phase, no result line."""
    with pytest.raises(SystemExit) as stop:
        chip_smoke.main([])
    assert stop.value.code not in (0, None)
    assert "needs a TPU" in str(stop.value.code) and "'cpu'" in str(stop.value.code)
    assert capsys.readouterr().out == ""


def test_infer_phase_rehearsal(capsys):
    line = chip_smoke.phase_infer(TINY, SEED)
    assert _lines(capsys) == [line]
    assert line["phase"] == "infer" and line["ok"] is True
    assert line["hw"] == [64, 96] and line["iters"] == 2
    assert line["seconds_per_map"] > 0 and line["compile_s"] > 0
    assert line["lookup_max_abs_err_float32"] <= 1e-4
    assert line["lookup_max_abs_err_bfloat16"] <= 1e-4
    assert line["model_pallas_vs_reg_max_abs_px"] <= 1e-2
    assert line["bf16_storage_epe_budget_px"] == 0.05
    assert line["kernel_calls"] == 0  # interpreted here; > 0 is checked on the chip


def test_serve_phase_rehearsal(capsys, tmp_path):
    line = chip_smoke.phase_serve(TINY, SEED, str(tmp_path))
    assert _lines(capsys) == [line]
    assert line["phase"] == "serve" and line["ok"] is True
    assert line["bucket"] == [64, 96] and line["max_batch"] == 2
    # prelude/chunk/finalize x batch 1, 2 x one bucket
    assert line["aot_entries"] == line["cold_boot_compiles"] == 6
    assert line["warm_boot_compiles"] == 0


def test_train_phase_rehearsal(capsys, tmp_path):
    line = chip_smoke.phase_train(TINY, SEED, str(tmp_path))
    assert _lines(capsys) == [line]
    assert line["phase"] == "train" and line["ok"] is True
    assert line["batch"] == 2 and len(line["losses"]) == 3
    assert len(set(line["losses"])) == 3
    report = os.path.join(str(tmp_path), "logs", "chip-smoke", "run_report.json")
    with open(report) as f:
        assert json.load(f)["exit_code"] == 0


@pytest.mark.slow
def test_four_chip_phases_rehearsal(capsys, tmp_path):
    """Second rehearsal: the --chips 4 paths on four virtual devices — the
    mesh, the sharding rules, the kernels' shard_map, the per-device AOT
    entries of the fleet. About a minute here, so outside tier-1: run it
    (`--runslow`) before a four-chip call."""
    assert len(jax.devices()) >= 4
    dp = chip_smoke.phase_train_dp(TINY, SEED, str(tmp_path), chips=4)
    fleet = chip_smoke.phase_serve_fleet(TINY, SEED, str(tmp_path), chips=4)
    assert _lines(capsys) == [dp, fleet]
    assert dp["phase"] == "train-dp" and dp["mesh"] == [4, 1]
    assert dp["global_batch"] == 4 and dp["state_devices"] == [0, 1, 2, 3]
    assert dp["loss_rel_diff"] <= 2e-2
    assert fleet["phase"] == "serve-fleet" and fleet["replicas"] == 4
    assert sorted(fleet["replica_devices"]) == [0, 1, 2, 3]
    assert fleet["requests"] == 8 and sum(fleet["batches_by_replica"].values()) == 8
    assert fleet["warm_boot_compiles"] == 0


# --- the compile-cache helper ----------------------------------------------


@pytest.fixture
def cache_dir_config():
    """Restore jax's cache-directory setting after a test moved it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins_and_nothing_is_set_in_code(
    monkeypatch, tmp_path, cache_dir_config
):
    from raft_stereo_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.setup_compile_cache() == "/some/dir"
    # Not even `train --compilation_cache_dir` overrides the variable.
    assert compile_cache.setup_compile_cache(str(tmp_path)) == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists("/some/dir")


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
    monkeypatch, tmp_path, cache_dir_config
):
    from raft_stereo_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    first = compile_cache.setup_compile_cache()
    assert first == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.setup_compile_cache() == first  # same across calls
    # The train flag names another directory only where the variable is unset.
    assert compile_cache.setup_compile_cache(str(tmp_path)) == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
