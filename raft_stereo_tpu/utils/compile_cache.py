"""The one place the persistent XLA compilation cache directory is chosen.

Every entry point that compiles (`train`, `evaluate`, `serve`,
`benchmark/run.py`, `chip_smoke.py`) calls `setup_compile_cache()` before its first compile, so a second run from the same checkout finds what
the first compiled. The directory is part of the cache key's world: one that
moves (a temporary name, a pid, a time) never hits.

- `JAX_COMPILATION_CACHE_DIR` set: jax reads it itself; nothing is set in
  code, and no option of this program overrides it.
- Otherwise: `requested` (`train --compilation_cache_dir`) if given, else the
  fixed `.jax_cache/` at the root of this checkout (listed in `.gitignore`).

It is also where the cache KEY is kept independent of who compiles. A Pallas
kernel's module is serialized into the program with its operations'
locations, and by default a location holds up to ten frames of the Python
stack: a kernel traced near the top of a short stack (the lookup's backward,
called from the custom VJP) then carries frames of whatever called `fit`, and
the same program lowered from another place (`obs.scopes`' printers,
`Trainer.hlo_audit_record`, another driver script) has other bytes and misses
the cache. `setup_compile_cache()` therefore limits a location's traceback to
its innermost frame, the same from every caller. Locations are debug
information; the compiled code is unchanged, and every instruction keeps its
`op_name` (turning tracebacks off altogether,
`jax_include_full_tracebacks_in_locations`, does NOT: it drops the name
stack from `op_name`, and with it every scope `obs.scopes` reads).

The serving tier's AOT executable cache (`serve --aot_cache_dir`,
serving/aot.py) is a different store: it holds whole serialized executables
keyed on the serving config, and a warm one skips tracing as well.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def setup_compile_cache(requested: Optional[str] = None) -> str:
    """Point jax at the persistent compilation cache; returns the directory
    in use. Idempotent, and safe to call before or after `import jax`
    touches a backend — but call it before the first compile."""
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 1)
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    cache_dir = os.path.abspath(requested or REPO_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
