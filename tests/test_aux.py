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
