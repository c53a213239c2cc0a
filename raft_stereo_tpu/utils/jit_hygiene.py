"""Runtime jit-hygiene enforcement: recompile monitoring + transfer guards.

graftlint (tools/graftlint) forbids the hazard PATTERNS statically; this
module proves the running step loop is actually free of the two hazards no
AST pass can see end-to-end:

- **steady-state recompiles**: a shape/dtype/static-arg leak makes jit
  silently re-trace mid-run — every ConvGRU step then pays seconds of XLA
  compile instead of milliseconds of device work, and nothing fails. The
  `RecompileMonitor` counts real backend compiles via jax's monitoring
  events (`/jax/core/compile/backend_compile_duration` fires once per
  compile, never on a cache hit) and — under strict mode — hard-fails the
  run on ANY compile after the first `recompile_grace` steps, outside
  explicitly whitelisted windows (validation/checkpoint compiles are
  legitimate and labelled).
- **silent host syncs**: `float(metrics[...])`, stray `np.asarray`, a debug
  f-string — each blocks the host on the device stream and kills async
  dispatch. Under strict mode the training loop runs inside
  `jax.transfer_guard("disallow")`: implicit transfers
  RAISE at the exact offending line, while the sanctioned explicit fetches
  (`jax.device_get` in the nan-flag drain and metrics flush, `device_put`
  in shard_batch) remain legal. Host-side I/O windows that legitimately
  move data (checkpoint save, validation, rollback restore) are opened with
  `whitelist(label)`, which also excuses their compiles — every window is
  counted per label and surfaced in the run report.

The trainer wires this into fit() (config knobs `strict_mode`,
`recompile_grace`; CLI `--strict_mode`) and publishes the counters as the
additive `jit_hygiene` block of run_report.json, so an orchestrator — or
the tier-1 strict-mode test — can assert "zero post-grace recompiles, zero
non-whitelisted transfers" from the report alone.

CPU/TPU neutral: the monitoring events and transfer guards are backend-
independent, so the tier-1 CPU run proves the same properties the TPU run
relies on.
"""

from __future__ import annotations

import contextlib
import logging
import threading
from typing import Dict, Iterator, List, Optional

logger = logging.getLogger(__name__)

# Fires exactly once per XLA backend compile (trace-cache hits are silent).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RecompileError(RuntimeError):
    """A compile happened after the grace window in strict mode — some
    input's shape/dtype/static key churns per step and every step is paying
    trace+compile. The message carries the step and window label context."""


class RecompileMonitor:
    """Counts backend-compile events against a step-indexed grace window.

    Usage::

        with RecompileMonitor(grace_steps=2) as mon:
            for step in ...:
                train_step(...)
                mon.advance(step)        # raises RecompileError post-grace
                with mon.allow("validation"):
                    validate()           # compiles here are excused

    `advance(step)` marks a step boundary: once the boundary of step
    `grace_steps` has passed (`steps_seen >= grace_steps`; grace 0 excuses
    nothing), any compile outside an `allow()` window is a violation; with
    `hard_fail` (strict mode) the next `advance` raises.
    The monitor is also usable as a plain counter (`hard_fail=False`) — the
    trainer always runs one so run_report.json carries compile counts even
    without strict mode, and the cached-init regression test
    (tests/test_jit_hygiene.py) asserts on `compiles_total` deltas.

    Listener registration is process-global in jax; enter/exit (or
    start/stop) pair it correctly even with several monitors alive — each
    instance filters its own accounting.
    """

    def __init__(self, grace_steps: int = 2, hard_fail: bool = False, label: str = "run"):
        self.grace_steps = int(grace_steps)
        self.hard_fail = bool(hard_fail)
        self.label = label
        self.compiles_total = 0
        self.compiles_post_grace = 0
        self.compiles_whitelisted = 0
        self.steps_seen = 0
        # grace<=0 means NO compile is ever excused (outside allow windows),
        # including ones landing before the first advance().
        self._post_grace = self.grace_steps <= 0
        self._allow_depth = 0
        self._violations: List[str] = []
        self._lock = threading.Lock()
        self._registered = False
        # Observability hook: called as (duration_s, whitelisted: bool,
        # post_grace: bool) for every compile event, OUTSIDE self._lock —
        # the flight recorder turns each compile into a trace event, so a
        # dump shows when (and whether legitimately) the run compiled.
        self.on_compile = None

    # -- listener plumbing -------------------------------------------------
    def _on_event(self, name: str, duration: float, **kwargs) -> None:
        if name != COMPILE_EVENT:
            return
        with self._lock:
            self.compiles_total += 1
            whitelisted = self._allow_depth > 0
            post_grace = not whitelisted and self._post_grace
            if whitelisted:
                self.compiles_whitelisted += 1
            elif post_grace:
                self.compiles_post_grace += 1
                self._violations.append(
                    f"compile after step {self.steps_seen} "
                    f"(grace={self.grace_steps}, label={self.label})"
                )
        hook = self.on_compile
        if hook is not None:
            try:
                hook(float(duration), whitelisted, post_grace)
            except Exception:  # noqa: BLE001 - observability is best-effort
                pass

    def start(self) -> "RecompileMonitor":
        if not self._registered:
            import jax

            jax.monitoring.register_event_duration_secs_listener(self._on_event)
            self._registered = True
        return self

    def stop(self) -> None:
        if not self._registered:
            return
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)
        self._registered = False

    def __enter__(self) -> "RecompileMonitor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- step protocol -----------------------------------------------------
    def advance(self, step: Optional[int] = None) -> None:
        """Mark a step boundary. Raises RecompileError (hard_fail only) if a
        non-whitelisted compile landed after the grace window. The window is
        exactly the first `grace_steps` steps: once the boundary of step
        `grace_steps` passes, every later compile is a violation."""
        self.steps_seen += 1
        if self.steps_seen >= self.grace_steps:
            self._post_grace = True
        if self.hard_fail and self._violations:
            detail = "; ".join(self._violations[:3])
            raise RecompileError(
                f"steady-state recompile detected at step "
                f"{step if step is not None else self.steps_seen}: {detail} — "
                "an input's shape/dtype/static argument churns per step "
                "(run scripts/lint.py, check batch shapes and weak types); "
                "raise recompile_grace only if late compiles are expected"
            )

    @contextlib.contextmanager
    def allow(self, label: str = "whitelisted") -> Iterator[None]:
        """Excuse compiles inside the block (validation / checkpoint / any
        labelled window where late compilation is legitimate)."""
        with self._lock:
            self._allow_depth += 1
        try:
            yield
        finally:
            with self._lock:
                self._allow_depth -= 1

    @property
    def violations(self) -> List[str]:
        with self._lock:
            return list(self._violations)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "compiles_total": self.compiles_total,
                "compiles_post_grace": self.compiles_post_grace,
                "compiles_whitelisted": self.compiles_whitelisted,
                "steps_seen": self.steps_seen,
            }

    def snapshot(self) -> Dict[str, object]:
        """Counters AND violations under one lock acquisition — a compile
        event landing between separate reads could otherwise yield
        compiles_post_grace != len(violations), which the run-report
        validator rejects (the report is built from a watchdog thread on
        hang exits, racing the main thread's compile)."""
        with self._lock:
            return {
                "compiles_total": self.compiles_total,
                "compiles_post_grace": self.compiles_post_grace,
                "compiles_whitelisted": self.compiles_whitelisted,
                "steps_seen": self.steps_seen,
                "violations": list(self._violations),
            }


class JitHygiene:
    """The trainer-facing bundle: transfer guard + recompile monitor +
    per-label whitelist accounting, reported as run_report.json's
    `jit_hygiene` block.

    `guard()` wraps the whole training loop; `whitelist(label)` opens the
    sanctioned host-transfer/compile windows inside it. Non-strict mode
    keeps the monitor counting (free observability) but guards nothing and
    never fails."""

    def __init__(self, strict: bool = False, recompile_grace: int = 2):
        self.strict = bool(strict)
        self.recompile_grace = int(recompile_grace)
        self.monitor = RecompileMonitor(
            grace_steps=recompile_grace, hard_fail=self.strict, label="train"
        )
        self.whitelisted_windows: Dict[str, int] = {}

    @contextlib.contextmanager
    def guard(self) -> Iterator[None]:
        """Loop-wide context: monitor always; `transfer_guard("disallow")`
        under strict mode (implicit device<->host transfers raise at the
        offending line; explicit device_get/device_put stay legal)."""
        with contextlib.ExitStack() as stack:
            stack.enter_context(self.monitor)
            if self.strict:
                import jax

                stack.enter_context(jax.transfer_guard("disallow"))
                logger.info(
                    "strict jit-hygiene: transfer_guard=disallow, hard-fail "
                    "on recompiles after %d steps", self.recompile_grace,
                )
            yield

    @contextlib.contextmanager
    def whitelist(self, label: str) -> Iterator[None]:
        """A sanctioned fetch/compile window (checkpoint save, validation,
        rollback restore, final fetch). Counted per label so the report
        shows exactly where the run is allowed to touch the host."""
        self.whitelisted_windows[label] = self.whitelisted_windows.get(label, 0) + 1
        with contextlib.ExitStack() as stack:
            stack.enter_context(self.monitor.allow(label))
            if self.strict:
                import jax

                stack.enter_context(jax.transfer_guard("allow"))
            yield

    @contextlib.contextmanager
    def transfer_window(self, label: str) -> Iterator[None]:
        """A labelled transfer-only window for a BACKGROUND thread (the
        device prefetcher, data/prefetch.py). `jax.transfer_guard` scopes
        are thread-local, so a worker thread is never inside the loop's
        strict `disallow` — this window makes its sanctioned device_puts
        explicit (counted in `whitelisted_windows` like any other) WITHOUT
        opening `monitor.allow`: the monitor's allow-depth is shared across
        threads, and excusing compiles from a long-lived prefetch thread
        would mask genuine step-loop recompiles for its whole lifetime."""
        self.whitelisted_windows[label] = self.whitelisted_windows.get(label, 0) + 1
        with contextlib.ExitStack() as stack:
            if self.strict:
                import jax

                stack.enter_context(jax.transfer_guard("allow"))
            yield

    def step(self, step: Optional[int] = None) -> None:
        """Per-iteration boundary: raises RecompileError under strict mode
        when a non-whitelisted post-grace compile happened."""
        self.monitor.advance(step)

    def report(self) -> Dict[str, object]:
        """The additive `jit_hygiene` run-report block
        (utils/run_report.py documents the schema)."""
        return {
            "strict_mode": self.strict,
            "recompile_grace": self.recompile_grace,
            "transfer_guard": "disallow" if self.strict else "off",
            **self.monitor.snapshot(),
            "whitelisted_windows": dict(self.whitelisted_windows),
        }
