"""Persistent AOT executable cache: boot loads executables, never traces.

Cold boot is the serving tier's largest MTTR term: every (bucket, batch) ×
(prelude, chunk, finalize) × replica combination is traced and XLA-compiled
from source, which costs seconds per executable — minutes fleet-wide. The
compiled artifacts are deterministic functions of the model config and the
toolchain, so this module persists them across processes: `warm()` asks the
cache first, and a populated cache turns boot into a sequence of
deserialize-and-load calls that fire ZERO backend-compile events (the
RecompileMonitor proves it — `--warmup_only --require_cache_hit` is the CI
form of that proof).

Key structure
-------------
A cache **fingerprint** names everything that invalidates every entry at
once — jax/jaxlib versions, backend platform, device kind and count, the
bucket table, warmed batch sizes, chunk/max iters, the full model config,
and the sharding preset. Entries live under `cache_dir/<fingerprint>/`, so
a toolchain upgrade or config change simply misses into a fresh directory
and never deserializes an incompatible artifact. Within a fingerprint
directory, the **entry key** names one executable: stage, bucket, batch,
prelude variant (plain vs warm-start), and the placement tag (`host` for
the uncommitted single-engine path, `d<id>` for a fleet replica committed
to device <id> — the serialized executable encodes its device assignment,
so replica entries are per-device by construction).

Failure policy
--------------
A cache must never make boot LESS reliable than tracing. Every load error —
unreadable file, unpicklable payload, embedded-fingerprint mismatch,
deserialize rejection — is handled identically: the entry is EVICTED (file
unlinked) with a loud warning, the miss is counted, and the caller falls
back to trace-and-compile, rewriting the entry for the next boot. Corrupt
caches therefore self-heal and can never crash or wedge a boot.

`stats()` feeds /healthz, the Prometheus gauges and the service's boot block:
`entries == cache_hits + cache_misses` (every warmup lookup is exactly one
of the two), which tests/report_checks.py `validate_boot` asserts.
"""

from __future__ import annotations

import hashlib
import io
import json
import logging
import os
import pickle
import threading
from typing import Dict, Optional, Tuple

import jax
from jax._src import compiler as _jax_compiler
from jax.experimental import serialize_executable as _jax_serialize

logger = logging.getLogger(__name__)

# Bump when the on-disk entry layout changes: stale-format entries then
# mismatch on load and are evicted/rewritten instead of misparsed.
# v2: entries carry an optional "audit" snapshot (tools/graftaudit record of
# HLO text + carried-state shardings captured at store() time), so cache-HIT
# boots can replay the audit without re-lowering — deserialized executables
# do not reliably expose as_text(). The format version feeds the cache
# fingerprint, so v1 directories simply become unreachable and v2 entries
# are written fresh (self-healing, no migration).
_FORMAT_VERSION = 2


class _RetargetingUnpickler(_jax_serialize._JaxPjrtUnpickler):
    """jax's executable unpickler, handing the runtime the device assignment
    too. `deserialize_executable` takes the devices an executable is LOADED
    onto from its CompileOptions argument (that is how jax's own persistent
    cache loads an entry onto the devices of the current request);
    `execution_devices` alone only sets what Python believes. jax's
    `deserialize_and_load` passes no options, and on a TPU every executable
    then lands on device 0 — a replica on another chip fails its first call
    ("Buffer passed to Execute() ... is on device TPU_1, but replica is
    assigned to device TPU_0"; seen on four chips, invisible on the CPU)."""

    def __init__(self, file, devices):
        super().__init__(file, devices[0].client, devices)
        self._compile_options = _jax_compiler.get_compile_options(
            num_replicas=1,
            num_partitions=len(devices),
            device_assignment=[[d.id for d in devices]],
            use_spmd_partitioning=len(devices) > 1,
        )

    def persistent_load(self, pid):
        if pid[0] == "exec":
            return self.backend.deserialize_executable(
                pid[1],
                executable_devices=self.execution_devices,
                compile_options=self._compile_options,
            )
        return super().persistent_load(pid)


def _deserialize_and_load(payload, in_tree, out_tree, devices):
    """`jax.experimental.serialize_executable.deserialize_and_load` onto
    `devices`, through `_RetargetingUnpickler`."""
    unloaded, args_info_flat, no_kwargs = _RetargetingUnpickler(
        io.BytesIO(payload), list(devices)
    ).load()
    return jax.stages.Compiled(
        unloaded.load(), [], in_tree.unflatten(args_info_flat), out_tree,
        no_kwargs=no_kwargs,
    )


def config_fingerprint(config) -> str:
    """Hex digest naming the (toolchain, topology, serving-config) world an
    executable was compiled in. Any difference — jaxlib upgrade, different
    device kind, edited bucket table, changed model width — changes the
    digest, so incompatible artifacts are unreachable rather than detected.
    """
    import jaxlib

    devices = jax.local_devices()
    material = {
        "format": _FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "none",
        "device_count": len(devices),
        "buckets": [list(hw) for hw in config.buckets],
        "batch_sizes": list(config.batch_sizes),
        "chunk_iters": config.chunk_iters,
        "max_iters": config.max_iters,
        "sharding_rules": config.sharding_rules,
        "video": config.video is not None,
        # repr of the frozen model dataclass covers every architectural
        # knob (dims, iters, channel widths) in one stable string.
        "model": repr(config.model),
    }
    blob = json.dumps(material, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def entry_key(
    stage: str,
    bucket: Tuple[int, int],
    batch: int,
    *,
    warm_start: bool = False,
    device_tag: str = "host",
) -> str:
    """One executable's name inside a fingerprint directory."""
    suffix = "-warm" if warm_start else ""
    return f"{stage}-{bucket[0]}x{bucket[1]}-b{batch}{suffix}-{device_tag}"


class ExecutableCache:
    """Disk-backed store of serialized XLA executables for one fingerprint.

    `load(key)` → a ready-to-call loaded executable, or None (miss — caller
    compiles and `store()`s). Thread-safe counters; the file operations are
    per-key so concurrent replica warmups touching DIFFERENT keys never
    contend, and same-key races at worst rewrite an identical artifact.
    """

    def __init__(self, cache_dir: str, config) -> None:
        self.fingerprint = config_fingerprint(config)
        self.root = os.path.join(os.path.expanduser(str(cache_dir)), self.fingerprint)
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        self.cache_hits = 0
        self.cache_misses = 0
        self.evictions = 0
        self.stores = 0
        # key → audit snapshot from the most recent load() hit (None when
        # the entry predates auditing); read via audit_snapshot().
        self._audit: Dict[str, Optional[dict]] = {}

    def _path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.aotx")

    def _evict(self, key: str, why: str) -> None:
        """Loudly drop a bad entry; the caller's trace-and-compile fallback
        rewrites it, so eviction is self-healing, never fatal."""
        path = self._path(key)
        try:
            os.unlink(path)
        except OSError:
            pass
        with self._lock:
            self.evictions += 1
        logger.warning(
            "aot cache: evicted entry %s (%s) — falling back to "
            "trace-and-compile, entry will be rewritten", key, why,
        )

    # -- lookup ------------------------------------------------------------
    def load(self, key: str, execution_devices):
        """Deserialize-and-load the entry onto `execution_devices` — the
        device(s) the executable was compiled for, which the engine knows —
        or None on miss/corruption. Left to jax's defaults it would load
        onto ALL local devices (a one-device executable then fails at its
        first call, "expected N shards") and, on a TPU, always onto device 0
        (`_RetargetingUnpickler`). Never raises: every failure mode evicts
        and reports a miss."""
        path = self._path(key)
        if not os.path.exists(path):
            with self._lock:
                self.cache_misses += 1
            return None
        try:
            with open(path, "rb") as fh:
                entry = pickle.load(fh)
            if not isinstance(entry, dict) or entry.get("format") != _FORMAT_VERSION:
                raise ValueError(f"unknown entry format {type(entry).__name__}")
            if entry.get("fingerprint") != self.fingerprint:
                raise ValueError(
                    f"embedded fingerprint {entry.get('fingerprint')!r} != "
                    f"{self.fingerprint!r} (version/topology mismatch)"
                )
            fn = _deserialize_and_load(
                entry["payload"],
                entry["in_tree"],
                entry["out_tree"],
                execution_devices,
            )
        except Exception as exc:  # noqa: BLE001 — any corruption = evict
            self._evict(key, repr(exc))
            with self._lock:
                self.cache_misses += 1
            return None
        with self._lock:
            self.cache_hits += 1
            self._audit[key] = entry.get("audit")
        return fn

    def audit_snapshot(self, key: str) -> Optional[dict]:
        """Audit record saved alongside the executable, for the most recent
        load() HIT of `key`; None when absent (entry stored unaudited)."""
        with self._lock:
            return self._audit.get(key)

    # -- populate ----------------------------------------------------------
    def store(self, key: str, compiled, audit: Optional[dict] = None) -> bool:
        """Serialize a freshly compiled executable into the cache. Best
        effort: serialization failures (backend without executable
        serialization, read-only dir) log and return False — the running
        engine keeps its in-memory executable either way. `audit` is the
        tools/graftaudit snapshot captured at compile time (None when the
        engine warmed without hlo_audit); it rides in the entry so later
        cache-hit boots can audit this executable."""
        try:
            payload, in_tree, out_tree = _jax_serialize.serialize(compiled)
            entry = {
                "format": _FORMAT_VERSION,
                "fingerprint": self.fingerprint,
                "key": key,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "audit": audit,
            }
            tmp = self._path(key) + ".tmp"
            with open(tmp, "wb") as fh:
                pickle.dump(entry, fh)
            os.replace(tmp, self._path(key))  # atomic: readers never see a torn file
        except Exception as exc:  # noqa: BLE001 — cache writes are optional
            logger.warning("aot cache: could not store %s: %r", key, exc)
            return False
        with self._lock:
            self.stores += 1
        return True

    # -- observability -----------------------------------------------------
    def stats(self) -> Dict[str, object]:
        """`entries` is lookups attempted (hits + misses) — the identity
        tests/report_checks.py `validate_boot` pins."""
        with self._lock:
            return {
                "enabled": True,
                "dir": self.root,
                "fingerprint": self.fingerprint,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "entries": self.cache_hits + self.cache_misses,
                "evictions": self.evictions,
                "stores": self.stores,
            }

    def files(self) -> int:
        """On-disk entry count for this fingerprint (bench/tests)."""
        try:
            return sum(1 for n in os.listdir(self.root) if n.endswith(".aotx"))
        except OSError:
            return 0


def maybe_cache(cache_dir: Optional[str], config) -> Optional["ExecutableCache"]:
    """ExecutableCache when a dir is configured and usable; None otherwise
    (engines keep the plain jit path) — an unwritable cache directory
    degrades to trace-at-boot, never to a crash."""
    if not cache_dir:
        return None
    try:
        return ExecutableCache(cache_dir, config)
    except OSError as exc:
        logger.warning("aot cache: cannot use %s (%r) — disabled", cache_dir, exc)
        return None


__all__ = [
    "ExecutableCache",
    "config_fingerprint",
    "entry_key",
    "maybe_cache",
]
