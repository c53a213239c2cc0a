"""Device prefetch — the training I/O spine's read half.

The DataLoader (data/loader.py) already overlaps host-side decode/augment
with device compute through its bounded prefetch queue, but the final hop —
`ShardingEngine.place_batch` (host numpy → device arrays on the mesh) — runs
on the consumer thread, serialized with the step dispatch. At multi-chip
batch sizes that transfer is whole milliseconds of device idle per step.

`DevicePrefetcher` wraps the loader and stages batch N+1 ON DEVICE while
step N runs: a producer thread pulls host batches, places them through the
SAME `place_batch` the trainer would have used (dp / spatial / multiprocess
`make_array_from_process_local_data` paths alike — no second placement
implementation to drift), and hands them over through a maxsize-1 queue —
the double-buffer shape the serving batcher already proved. Zero new
executables: placement is `jax.device_put` / array assembly, never a trace;
the strict-mode acceptance test asserts `compiles_post_grace == 0` with the
prefetcher on.

Transfer-guard interaction: `jax.transfer_guard` is thread-local, so the
trainer's strict-mode `disallow` scope never covers this producer thread —
its device_puts are sanctioned by construction. The window is still made
explicit: each epoch's producer runs inside the hygiene's labelled
`device_prefetch` transfer window, so run_report.json's
`whitelisted_windows` records that the run moves data here, same as the
checkpoint/validation windows.

Crash-consistent resume: the loader advances its stream cursor when a batch
is HANDED OFF, which with a prefetcher in between is one batch ahead of what
the trainer has actually stepped on. The producer therefore snapshots
`loader.state_dict()` immediately after each pull and the snapshot travels
WITH its batch; `state_dict()` serves the snapshot matching the batch the
consumer currently holds — so a checkpoint taken inside the step loop
records exactly the cursor an unwrapped loader would have, and the
batch-exact resume proof (tests/test_crash_recovery.py) holds unchanged.

Every other loader attribute (quarantine, load_state_dict, resilience_stats,
set_global_budget_mode, close, ...) proxies through untouched, so the
trainer's run-state and budget plumbing cannot tell the wrapper from the
loader.
"""

from __future__ import annotations

import contextlib
import logging
import queue
import threading
from typing import Any, Dict, Iterator, Optional

logger = logging.getLogger(__name__)

# The device-bound batch keys (the trainer's step consumes exactly these;
# host-only fields like "paths" stay on the host side of the hop).
BATCH_KEYS = ("image1", "image2", "flow", "valid")


class DevicePrefetcher:
    """Double-buffered device staging around a DataLoader.

    Iterating yields batches ALREADY placed on the mesh (dicts of jax arrays
    keyed by BATCH_KEYS) — the trainer must skip its own `place_batch` for
    batches coming from here. `stats()` reports the health counters for the
    run report's `io_spine` block: the queue depth watermark and the
    fraction of consumer fetches that found the next batch already staged
    (i.e. the transfer genuinely overlapped the step)."""

    def __init__(self, loader: Any, sharding: Any, hygiene: Optional[Any] = None, batch_keys=BATCH_KEYS):
        self._batch_keys = tuple(batch_keys)
        self._loader = loader
        self._sharding = sharding
        self._hygiene = hygiene
        self._state_snapshot: Optional[Dict] = None
        self._depth_watermark = 0
        self._overlap_hits = 0
        self._fetches = 0
        self._lock = threading.Lock()

    # --- loader proxy -----------------------------------------------------
    def __getattr__(self, name: str) -> Any:
        return getattr(self._loader, name)

    def __len__(self) -> int:
        return len(self._loader)

    @property
    def state_dict(self):
        """The stream position matching the batch the CONSUMER holds — the
        producer-side snapshot taken at that batch's hand-off — not the
        loader's live cursor (which runs one staged batch ahead).

        A property returning a callable so that wrapping a plain iterable
        (no `state_dict`) keeps `hasattr(wrapper, "state_dict")` False —
        the trainer's run-state bundling keys on exactly that."""
        loader_fn = self._loader.state_dict  # AttributeError when unsupported

        def _state_dict() -> Dict:
            if self._state_snapshot is not None:
                return self._state_snapshot
            return loader_fn()

        return _state_dict

    def load_state_dict(self, state: Dict) -> None:
        self._state_snapshot = None
        self._loader.load_state_dict(state)

    # --- health counters --------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            fetches = self._fetches
            return {
                "prefetch_depth_watermark": int(self._depth_watermark),
                "device_put_overlap_fraction": (
                    float(self._overlap_hits) / fetches if fetches else 0.0
                ),
            }

    # --- iteration --------------------------------------------------------
    def __iter__(self) -> Iterator[Dict[str, Any]]:
        q: "queue.Queue" = queue.Queue(maxsize=1)
        stop = threading.Event()

        def producer() -> None:
            window = (
                self._hygiene.transfer_window("device_prefetch")
                if self._hygiene is not None
                else contextlib.nullcontext()
            )
            try:
                with window:
                    for batch in self._loader:
                        if stop.is_set():
                            break
                        arrays = {k: batch[k] for k in self._batch_keys}
                        placed = self._sharding.place_batch(arrays)
                        # Snapshot AFTER the pull: the loader's cursor now
                        # sits just past this batch, which is exactly what a
                        # checkpoint taken while the consumer steps on it
                        # must record (loader.state_dict contract). Plain
                        # iterables (no state_dict) carry no cursor.
                        snapshot = (
                            self._loader.state_dict()
                            if hasattr(self._loader, "state_dict")
                            else None
                        )
                        q.put((placed, snapshot))
                        if stop.is_set():
                            break
            except BaseException as e:
                if not isinstance(e, Exception):
                    e = RuntimeError(f"device prefetch aborted: {e!r}")
                q.put(e)
                return
            q.put(None)

        thread = threading.Thread(
            target=producer, name="device-prefetch", daemon=True
        )
        thread.start()
        try:
            while True:
                depth = q.qsize()
                item = q.get()
                if item is None:
                    break
                if isinstance(item, Exception):
                    raise item
                # Count only real-batch fetches (the end sentinel would
                # otherwise inflate the overlap fraction on short epochs).
                with self._lock:
                    self._fetches += 1
                    if depth > 0:
                        self._overlap_hits += 1
                    self._depth_watermark = max(self._depth_watermark, depth)
                placed, snapshot = item
                self._state_snapshot = snapshot
                yield placed
        finally:
            stop.set()
            # Drain so a producer blocked on q.put can observe stop and exit.
            while thread.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    if not thread.is_alive():
                        break
                    thread.join(timeout=0.1)
