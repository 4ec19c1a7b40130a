"""Chrome-trace timeline for eager collective lifecycles.

TPU-native rebuild of the reference's timeline writer
(ref: horovod/common/timeline.cc/.h [V], SURVEY.md §5.1): emits
``chrome://tracing`` JSON where each tensor is a "process" row and its
lifecycle phases are duration events. The reference's phases are kept —
NEGOTIATE_* is emitted with zero-ish duration since XLA removed the
negotiation round, documenting the semantic mapping rather than hiding it.

Activated by ``HOROVOD_TIMELINE=/path.json``; ``hvd.start_timeline()`` /
``hvd.stop_timeline()`` provide the runtime API added upstream in v0.21 [V].
When the native C runtime is available the event sink is the C++ ring
buffer (csrc/timeline_buffer.cc); otherwise a pure-Python writer is used.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List

# Lifecycle phase names, mirroring timeline.cc's event names [V].
NEGOTIATE = "NEGOTIATE_{}"
QUEUE = "QUEUE"
MEMCPY_IN_FUSION_BUFFER = "MEMCPY_IN_FUSION_BUFFER"
COMM = "{}"  # e.g. ALLREDUCE, ALLGATHER — on TPU the XLA/ICI collective
MEMCPY_OUT_FUSION_BUFFER = "MEMCPY_OUT_FUSION_BUFFER"
CYCLE_MARKER = "CYCLE"


class Timeline:
    """Thread-safe Chrome-trace event writer."""

    def __init__(self, path: str, mark_cycles: bool = False):
        self._path = path
        self._mark_cycles = mark_cycles
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._tensor_pids: Dict[str, int] = {}
        self._next_pid = 1
        self._t0 = time.perf_counter()
        self._active = True
        self._native = None
        try:
            from .._native import loader as _native_loader

            self._native = _native_loader.timeline_buffer()
        except Exception:
            self._native = None

    # -- runtime start/stop API (ref: horovod_start_timeline [V]) --

    def start(self) -> None:
        self._active = True

    def stop(self) -> None:
        """Deactivate AND flush the file: the reference writes the
        timeline incrementally, so after hvd.stop_timeline() the user
        can open the trace immediately — waiting for shutdown() to
        materialize it would silently diverge (timeline.cc [V]).
        start() may still resume recording; close() re-writes with any
        further events.

        The deactivation happens UNDER the emit lock: every emit path
        re-checks ``_active`` after acquiring the lock, so an emitter
        that raced past the cheap pre-check either lands its event
        before the flip (and the final ``_write`` below includes it) or
        observes the flip and drops the event entirely. Without this, a
        counter()/span() blocked on the lock could append its event
        AFTER stop()'s write — present in memory, silently missing from
        the file the user just opened."""
        with self._lock:
            self._active = False
        self._write()

    @property
    def active(self) -> bool:
        return self._active

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _pid(self, tensor_name: str) -> int:
        pid = self._tensor_pids.get(tensor_name)
        if pid is None:
            pid = self._next_pid
            self._next_pid += 1
            self._tensor_pids[tensor_name] = pid
            self._emit(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "args": {"name": tensor_name},
                }
            )
        return pid

    def _emit(self, event: dict) -> None:
        if self._native is not None:
            self._native.emit(json.dumps(event))
        else:
            self._events.append(event)

    def begin(self, tensor_name: str, phase: str) -> None:
        if not self._active:
            return
        with self._lock:
            if not self._active:  # lost the race with stop()'s flush
                return
            self._emit(
                {
                    "name": phase,
                    "ph": "B",
                    "pid": self._pid(tensor_name),
                    "ts": self._now_us(),
                }
            )

    def now_us(self) -> float:
        """Current trace-relative timestamp — for callers that measure
        a span themselves and stamp it via :meth:`span`."""
        return self._now_us()

    def span(
        self, tensor_name: str, phase: str, start_us: float, dur_us: float
    ) -> None:
        """Complete ('X') event with EXPLICIT timestamps. Used for the
        device-completion stamp on fused flushes (ops/fusion.py): the
        dispatch-side begin/end pairs record when the eager runtime
        QUEUED and launched the collective — the phase it owns — while
        this span carries the dispatch→`block_until_ready` delta, i.e.
        when the device actually finished. The traced path gets the
        same truth from the profiler (traced_timeline); this closes the
        eager half of SURVEY §7's device-completion checklist row."""
        if not self._active:
            return
        with self._lock:
            if not self._active:
                return
            self._emit(
                {
                    "name": phase,
                    "ph": "X",
                    "pid": self._pid(tensor_name),
                    "ts": float(start_us),
                    "dur": float(dur_us),
                }
            )

    def end(self, tensor_name: str, phase: str) -> None:
        if not self._active:
            return
        with self._lock:
            if not self._active:
                return
            self._emit(
                {
                    "name": phase,
                    "ph": "E",
                    "pid": self._pid(tensor_name),
                    "ts": self._now_us(),
                }
            )

    def instant(self, tensor_name: str, phase: str) -> None:
        if not self._active:
            return
        with self._lock:
            if not self._active:
                return
            self._emit(
                {
                    "name": phase,
                    "ph": "i",
                    "pid": self._pid(tensor_name),
                    "ts": self._now_us(),
                    "s": "p",
                }
            )

    def counter(self, name: str, value: float) -> None:
        """Chrome-trace counter track (ph "C") — the fusion manager
        feeds per-cycle gauges (bucket pad bytes, fused dispatches)
        here so padding/dispatch cost lines up with the per-tensor
        lifecycle rows in the same trace. The telemetry hub feeds its
        ``telemetry.step`` track through here at every step boundary so
        traces align with StepStats records (common/telemetry.py)."""
        if not self._active:
            return
        with self._lock:
            if not self._active:
                return
            self._emit(
                {
                    "name": name,
                    "ph": "C",
                    "pid": 0,
                    "ts": self._now_us(),
                    "args": {name: value},
                }
            )

    def mark_cycle(self) -> None:
        """One eager fusion-cycle boundary (HOROVOD_TIMELINE_MARK_CYCLES)."""
        if self._mark_cycles and self._active:
            with self._lock:
                if not self._active:
                    return
                self._emit(
                    {
                        "name": CYCLE_MARKER,
                        "ph": "i",
                        "pid": 0,
                        "ts": self._now_us(),
                        "s": "g",
                    }
                )

    def _write(self) -> None:
        with self._lock:
            if self._native is not None:
                # drain() empties the ring; keep drained events so a
                # later write (stop → close) still has the full trace
                self._events.extend(
                    json.loads(s) for s in self._native.drain()
                )
            events = self._events
            tmp = f"{self._path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"traceEvents": events}, f)
            os.replace(tmp, self._path)

    def close(self) -> None:
        self._write()
