"""Run a kernel-bearing function on the mesh a multi-device step is traced
under: each device on its own share of the batch.

XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be automatically
partitioned", raised by the chip's compiler for the data-parallel train
step), so a function that holds one is `shard_map`ped over the data axis:
operands whose leading axis is the batch (or rows that are batch-major) are
split there, which is the sharding they already have, so nothing is
resharded; the others (weights) are whole on every device, and their
gradients are summed over the axis by `shard_map`'s own transpose. `call`
must size its grid from the shapes it is handed (inside the map they are the
per-device shapes). The mesh is jax's context mesh (`jax.set_mesh`, entered
by `ShardingEngine.wrap` around every multi-device step), which is part of
the trace cache key. With none set, or one device, this is a plain call.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from raft_stereo_tpu.parallel.mesh import DATA_AXIS


def over_data_axis(call, split, whole=()):
    """`call(*split, *whole)` with every `split` operand and every result
    divided along its leading axis over the data axis."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return call(*split, *whole)
    devices = mesh.shape[DATA_AXIS]
    if mesh.size != devices:
        raise NotImplementedError(
            f"the Pallas kernels are wired for the data mesh axis only, and this mesh is {dict(mesh.shape)} "
            "(the stereo family: use corr_implementation='reg' with a spatial preset on several devices)"
        )
    if any(x.shape[0] % devices for x in split):
        raise ValueError(
            f"leading axes {[x.shape[0] for x in split]} (batch, or batch-major rows) do not divide over the "
            f"{devices} devices of the data axis"
        )
    specs = (P(DATA_AXIS),) * len(split) + (P(),) * len(whole)
    return jax.shard_map(call, in_specs=specs, out_specs=P(DATA_AXIS), check_vma=False)(*split, *whole)
