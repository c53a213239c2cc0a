"""Multi-host initialization — DCN-scale counterpart of the mesh layer.

The reference never goes multi-process (no torch.distributed anywhere;
SURVEY.md §2.3). This framework's multi-host story is standard JAX SPMD:
`jax.distributed.initialize()` connects the hosts, every process sees the
global device set, and the SAME mesh/pjit code from parallel/mesh.py spans
the pod — ICI carries collectives within a slice, DCN across slices. The
input pipeline shards per-host via DataLoader(host_id, num_hosts).

Call `init_multihost()` once at process start (before any jax device use).
On single-host setups it is a no-op, so entry points can call it
unconditionally.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

logger = logging.getLogger(__name__)


def init_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> dict:
    """Initialize jax.distributed when running multi-process.

    With no arguments, auto-detects from the environment (TPU pod runtime
    sets everything; explicit JAX_COORDINATOR_ADDRESS/NUM_PROCESSES/
    PROCESS_ID work for DCN clusters). Returns a summary dict:
    {process_index, process_count, local_devices, global_devices}.
    """
    explicit = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    n_proc = num_processes if num_processes is not None else _env_int("JAX_NUM_PROCESSES")
    if explicit or (n_proc and n_proc > 1):
        _enable_cpu_collectives()
    if explicit:
        jax.distributed.initialize(
            coordinator_address=explicit,
            num_processes=n_proc,
            process_id=process_id if process_id is not None else _env_int("JAX_PROCESS_ID"),
        )
    elif n_proc and n_proc > 1:
        # Cluster auto-detection (TPU pod runtime / SLURM) fills the rest in.
        jax.distributed.initialize()
    info = {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
    if info["process_count"] > 1:
        logger.info("multi-host initialized: %s", info)
    return info


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


def _enable_cpu_collectives() -> None:
    """Back multi-process CPU computations with gloo.

    On TPU the ICI/DCN fabric carries cross-process collectives natively,
    but the CPU backend refuses multi-process programs ("Multiprocess
    computations aren't implemented on the CPU backend") unless a CPU
    collectives implementation is selected BEFORE the backend is created.
    This is what lets the 2-process fault-coordination and sharded-step
    tests (tests/test_distributed.py) run the REAL SPMD code paths —
    device_put of replicated state, the pod-agreement all-reduce, the
    collective checkpoint save — on a laptop-grade CPU sandbox. No-op on
    non-CPU platforms."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] not in ("", "cpu"):
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def process_topology() -> tuple:
    """(process_index, process_count) — the one place the host topology is
    read, so tests can mock multi-host layouts (loader sharding, pod
    coordination, budget math) on a single process by patching here."""
    return jax.process_index(), jax.process_count()


def host_shard_args() -> dict:
    """(host_id, num_hosts) kwargs for DataLoader per-host input sharding."""
    index, count = process_topology()
    return {"host_id": index, "num_hosts": count}
