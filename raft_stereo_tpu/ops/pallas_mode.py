"""Where a Pallas kernel runs: the one decision every `pallas_call` shares.

On a TPU backend the kernels are compiled by Mosaic (`tpu_custom_call` in
the compiled program). On the CPU backend — the tier-1 tests and the
`JAX_PLATFORMS=cpu` rehearsals — they run in the Pallas interpreter so the
parity tests cover the same kernel bodies. Any other backend is an error:
interpreting there would silently replace the device path with a slow
emulation of it.
"""

from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """The `interpret=` argument for every `pl.pallas_call` in ops/."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' and interpreted on 'cpu' only; "
        f"the default JAX backend is {backend!r}"
    )
