"""Negative controls for the three report oracles (tests/report_checks.py).

tests/test_boot.py, test_frontier.py and test_rollout.py assert that the
program's reports pass the oracles; an oracle that passes everything would
satisfy them too. Here each oracle gets a sound block FROM THE PROGRAM, then
the same block with one field doctored, and must name that field.
"""

import copy

import pytest

from report_checks import validate_boot, validate_frontier, validate_rollout


@pytest.fixture(scope="module")
def blocks(tmp_path_factory):
    """One sound block an oracle: a tiny service's boot (three executables
    compiled into a fresh cache), and an idle two-backend front tier's
    metrics and rollout block (never started: no socket is opened)."""
    from raft_stereo_tpu.config import FrontierConfig, RAFTStereoConfig, ServeConfig
    from raft_stereo_tpu.serving.frontier import Frontier
    from raft_stereo_tpu.serving.service import StereoService

    model = RAFTStereoConfig(hidden_dims=(16, 16, 16), n_gru_layers=1, corr_levels=2, corr_radius=2)
    service = StereoService(ServeConfig(
        model=model, buckets=((32, 64),), max_batch=1, chunk_iters=1, max_iters=1,
        aot_cache_dir=str(tmp_path_factory.mktemp("aot")),
    )).start()
    try:
        boot = service.boot_block()
    finally:
        service.close()
    frontier = Frontier(FrontierConfig(backends=("127.0.0.1:1", "127.0.0.1:2")), sleep=lambda s: None)
    return {
        validate_boot: boot,
        validate_frontier: frontier.metrics(),
        validate_rollout: frontier.rollout_block(),
    }


def _set(**fields):
    return lambda block: block.update(fields)


def _counted_twice(block):
    block["responses_total"] = block["requests_total"] + 1


def _unbalanced(block):
    block["cache_hits"] += 1


def _foreign_state(block):
    block["backend_states"][0] = "zombie"


CASES = {
    "boot: a hit no lookup made": (validate_boot, _unbalanced, "ledger does not balance"),
    "boot: the timer never ran": (validate_boot, _set(warmup_seconds=0.0), "warmup_seconds must be > 0"),
    "boot: a flag for a count": (validate_boot, _set(respawns_total=True), "boot['respawns_total'] has type bool"),
    "frontier: a response counted twice": (validate_frontier, _counted_twice, "exactly-once ledger"),
    "frontier: a state outside the enum": (validate_frontier, _foreign_state, "backend_states[0] 'zombie' not in"),
    "frontier: half a percentile pair": (validate_frontier, _set(latency_p50_ms=1.0), "both null or both numeric"),
    "rollout: a phase outside the enum": (validate_rollout, _set(phase="flipping"), "phase 'flipping' not in"),
    "rollout: completed on two generations": (
        validate_rollout, _set(phase="completed", backend_generations=[0, 1], fleet_generation=0),
        "a completed roll leaves one generation"),
    "rollout: a verdict against its measurement": (
        validate_rollout, _set(mixed_generation_seconds=0.5), "contradicts mixed_generation_seconds"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_oracle_names_the_doctored_field(blocks, case):
    validate, doctor, names = CASES[case]
    assert validate(blocks[validate]) == []
    block = copy.deepcopy(blocks[validate])
    doctor(block)
    errors = validate(block)
    assert len(errors) == 1 and names in errors[0], errors
