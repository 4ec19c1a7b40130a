"""Fleet trace plane: cross-host request/step spans (stdlib only).

The flight recorder (common/telemetry.py) answers "what did THIS worker
do on step N"; since the serving plane went disaggregated a single
request traverses client → Router → prefill worker → int8 KV transfer →
decode worker, and may be replayed, hedged, or live-migrated mid-decode
— no per-worker instrument can say where ITS time went. This module is
the correlation layer: W3C-traceparent-style contexts minted at
``POST /generate`` (or adopted from an incoming header), child spans
recorded into a bounded per-worker ring, and NTP-style clock stamps on
every hop so ``analysis/trace_merge.py`` can assemble one
skew-corrected chrome://tracing view of the whole fleet.

Design constraints, in order:

1. **Zero cost when off.** ``HOROVOD_TRACE`` defaults off and sampling
   is decided ONCE at mint — every downstream carrier holds an
   ``Optional[TraceContext]`` and skips span creation entirely on
   ``None``. A span costs two ``time.monotonic()`` stamps and a dict;
   nothing here runs on the decode hot path per token, so the
   zero-retrace invariant (decode_compiles==1) is untouched.
2. **Stdlib only.** Contexts ride HTTP headers (``traceparent``) and a
   ``trace`` field in the kv_transfer JSON meta frames; no OTLP, no
   exporter threads.
3. **Crash-safe.** The span ring drains beside the StepStats ring: the
   telemetry hub's atexit/SIGTERM dump also writes
   ``<flight_recorder>.spans`` as JSON-lines, so a SIGTERM'd worker
   leaves its spans on disk for ``scripts/trace_assemble.py``.

Knobs (typed in common/config.py, read via ``basics.live_config()``):
``HOROVOD_TRACE`` (master switch), ``HOROVOD_TRACE_SAMPLE`` (fraction
of minted roots that are sampled; descendants inherit the decision),
``HOROVOD_TRACE_SPANS`` (ring bound).

**Process spans** (:func:`span`, :func:`hot_span`, :func:`trace_time_span`)
need no request: their parent is the thread's active span or the
process root context that ``hvd.init`` mints once. They land in the
same ring in the same record shape. Names are ``hvd.<layer>.<what>``
(``hvd.init.*``, ``hvd.trainer.*``, ``hvd.exchange.*``,
``hvd.kernels.*``, ``hvd.batcher.*``, ``hvd.engine.*``;
docs/observability.md has the table). ``span`` is for work that happens
a bounded number of times a process (init, placement, trace time) and
always records; ``hot_span`` is for work per scheduler round or per idle
stretch and records only under ``HOROVOD_TRACE``, at
``HOROVOD_TRACE_SAMPLE``.

**The profiler's clock.** Every span that is *entered* (``with span``)
while a ``jax.profiler`` session runs is also written into that session
as ``TraceAnnotation("<name>#<seq>")``, so it sits on the device
trace's clock beside the device's operations; ``seq`` is in the ring
record too, which joins a trace event to its tags. Outside a session
the check is one atomic load, and JAX is never imported from here: the
annotation exists only when ``"jax" in sys.modules``. A span that is
begun on one thread and ended by hand on another (``serve.decode``)
cannot be a scoped annotation and stays in the ring alone.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import secrets
import socket
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

DEFAULT_SPAN_RING = 2048

TRACEPARENT_HEADER = "traceparent"
TRACE_ID_HEADER = "X-Trace-Id"
# hop skew stamps: servers echo their recv/send wall clocks + identity
# so clients can tag the NTP edge onto their hop span
TS_RECV_HEADER = "X-Trace-Ts-Recv"
TS_SEND_HEADER = "X-Trace-Ts-Send"
PEER_HEADER = "X-Trace-Peer"


class TraceContext:
    """trace_id / span_id pair in W3C trace-context shape.

    ``span_id`` is the id of the span this context BELONGS to — a child
    span minted under it uses it as ``parent_id``. ``sampled`` is the
    root's coin flip, inherited by every descendant so a trace is
    all-or-nothing across the fleet.
    """

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = bool(sampled)

    def to_traceparent(self) -> str:
        flag = "01" if self.sampled else "00"
        return f"00-{self.trace_id}-{self.span_id}-{flag}"

    def to_dict(self) -> Dict[str, object]:
        """Wire form for JSON payloads (kv_transfer meta frames,
        migrate records)."""
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "sampled": self.sampled,
        }

    @classmethod
    def from_dict(cls, d) -> Optional["TraceContext"]:
        if not isinstance(d, dict):
            return None
        tid = d.get("trace_id")
        sid = d.get("span_id")
        if not tid or not sid:
            return None
        return cls(str(tid), str(sid), bool(d.get("sampled", True)))

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"TraceContext({self.to_traceparent()})"


def parse_traceparent(header: Optional[str]) -> Optional[TraceContext]:
    """``00-{32 hex}-{16 hex}-{flags}`` → context; None on anything
    malformed (a bad header must never fail a request)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
        sampled = bool(int(flags, 16) & 1)
    except ValueError:
        return None
    if int(trace_id, 16) == 0 or int(span_id, 16) == 0:
        return None
    return TraceContext(trace_id.lower(), span_id.lower(), sampled)


def _new_trace_id() -> str:
    return secrets.token_hex(16)


def _new_span_id() -> str:
    return secrets.token_hex(8)


# ------------------------------------------------------------------ spans

_tls = threading.local()
_seq = itertools.count()  # next() is atomic under the GIL


def _profiler_annotation(name: str, seq: int):
    """An entered ``jax.profiler.TraceAnnotation("<name>#<seq>")`` while
    a profiler session runs, else None. JAX is never imported from
    here: a process that has not imported it has no session."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        cls = jax.profiler.TraceAnnotation
        if not cls.is_enabled():
            return None
        annotation = cls(f"{name}#{seq}")
        annotation.__enter__()
        return annotation
    except Exception:  # noqa: BLE001 - a span must never fail its caller
        return None


class Span:
    """One timed operation on one worker.

    Two monotonic stamps and a dict: ``begin`` records epoch + monotonic
    start, ``end`` closes the duration and appends the record to the
    process ring. Usable as a context manager (pushes itself onto the
    thread-local active stack so RetryPolicy can annotate the hop it is
    retrying under), or held across threads and ended manually.
    """

    __slots__ = (
        "name", "ctx", "parent_id", "tags", "ts", "seq", "_t0", "_done",
        "_annotation",
    )

    def __init__(
        self,
        name: str,
        ctx: TraceContext,
        parent_id: Optional[str],
        tags: Optional[dict] = None,
    ) -> None:
        self.name = name
        self.ctx = ctx  # ctx.span_id is THIS span's id
        self.parent_id = parent_id
        self.tags = dict(tags) if tags else {}
        # process-wide serial: the name of the span's profiler
        # annotation is "<name>#<seq>", so a trace event finds its tags
        self.seq = next(_seq)
        self._annotation = None
        self.ts = time.time()
        self._t0 = time.monotonic()
        self._done = False

    def annotate(self, note: str) -> None:
        """Append a breadcrumb (the retry ladder's site#attempt@backoff
        entries) without touching timing."""
        notes = self.tags.setdefault("notes", [])
        if len(notes) < 64:  # bounded — a hot retry loop can't balloon a span
            notes.append(note)

    def tag(self, **kv) -> None:
        self.tags.update(kv)

    def end(self, **kv) -> None:
        if self._done:
            return
        self._done = True
        if kv:
            self.tags.update(kv)
        dur_ms = (time.monotonic() - self._t0) * 1e3
        recorder().record(
            {
                "trace_id": self.ctx.trace_id,
                "span_id": self.ctx.span_id,
                "parent_id": self.parent_id,
                "name": self.name,
                "seq": self.seq,
                "ts": self.ts,
                "dur_ms": round(dur_ms, 3),
                "tags": self.tags,
            }
        )

    def discard(self) -> None:
        """Leave an entered span without a record: the thread's stack and
        a profiler session see it end, the ring does not (the compile
        ledger's events under its floor)."""
        self._done = True
        self.__exit__(None, None, None)

    # -- thread-local active-span stack (for retry annotations) --

    def __enter__(self) -> "Span":
        push_active(self)
        self._annotation = _profiler_annotation(self.name, self.seq)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        pop_active(self)
        if exc_type is not None and "outcome" not in self.tags:
            self.tags["outcome"] = "error"
            self.tags["error"] = f"{exc_type.__name__}: {exc}"
        self.end()


def push_active(span: Span) -> None:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(span)


def pop_active(span: Span) -> None:
    stack = getattr(_tls, "stack", None)
    if stack and stack[-1] is span:
        stack.pop()
    elif stack and span in stack:  # out-of-order end: drop it anyway
        stack.remove(span)


def current() -> Optional[Span]:
    """The innermost active span on THIS thread (None when tracing is
    off or no span is open) — the retry ladder's annotation target."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def annotate(note: str) -> None:
    """Annotate the active span, if any — safe to call unconditionally
    (the no-trace path is one thread-local read)."""
    span = current()
    if span is not None:
        span.annotate(note)


class active(object):
    """Context manager adopting an EXISTING span as this thread's
    active span (the kv_transfer handoff thread runs under the
    request's span without owning its lifetime)."""

    __slots__ = ("_span",)

    def __init__(self, span: Optional[Span]) -> None:
        self._span = span

    def __enter__(self):
        if self._span is not None:
            push_active(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._span is not None:
            pop_active(self._span)


# -------------------------------------------------------------- recorder


class SpanRecorder:
    """Bounded per-process span ring beside the StepStats ring.

    ``deque(maxlen=N)`` appends are atomic under the GIL, so concurrent
    emitters never grow past the bound; the lock only guards reads and
    reconfiguration. Drained by the telemetry hub's atexit/SIGTERM dump
    into ``<flight_recorder>.spans``.
    """

    def __init__(self, capacity: int = DEFAULT_SPAN_RING) -> None:
        self.capacity = max(int(capacity), 1)
        self._ring: "deque" = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.host = socket.gethostname()
        self.pid = os.getpid()
        self.role = ""

    def configure(
        self, capacity: Optional[int] = None, role: Optional[str] = None
    ) -> None:
        with self._lock:
            if capacity is not None and int(capacity) != self.capacity:
                self.capacity = max(int(capacity), 1)
                self._ring = deque(self._ring, maxlen=self.capacity)
            if role is not None:
                self.role = role

    def record(self, span_rec: dict) -> None:
        span_rec.setdefault("host", self.host)
        span_rec.setdefault("pid", self.pid)
        if self.role:
            span_rec.setdefault("role", self.role)
        self._ring.append(span_rec)  # atomic; no lock on the emit path

    def spans(self) -> List[dict]:
        with self._lock:
            for _ in range(3):
                try:
                    return [dict(r) for r in list(self._ring)]
                except RuntimeError:  # mutated during iteration
                    continue
            return []

    def __len__(self) -> int:
        return len(self._ring)

    def dump(self, path: str) -> Optional[str]:
        """JSON-lines, oldest first, tmp+rename (same crash discipline
        as the flight recorder)."""
        spans = self.spans()
        if not spans:
            return None
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            for rec in spans:
                f.write(json.dumps(rec) + "\n")
        os.replace(tmp, path)
        return path


# ------------------------------------------------------------- singleton

_recorder: Optional[SpanRecorder] = None
_rec_lock = threading.Lock()
# settings cache: (enabled, sample) — resolved once, reset by tests
_settings: Optional[tuple] = None


def _load_settings() -> tuple:
    global _settings
    cached = _settings
    if cached is not None:
        return cached
    from . import basics

    cfg = basics.live_config()
    _settings = (bool(cfg.trace), float(cfg.trace_sample))
    return _settings


def recorder() -> SpanRecorder:
    global _recorder
    with _rec_lock:
        if _recorder is None:
            from . import basics

            cfg = basics.live_config()
            _recorder = SpanRecorder(capacity=cfg.trace_spans)
        return _recorder


def set_role(role: str) -> None:
    """Stamp this process's serving role (prefill/decode/unified/…)
    onto every span it records — the assembler's row key."""
    recorder().configure(role=role)


def _reset() -> None:
    """Test hook: drop the recorder, the settings cache and the process
    root so the next call re-reads config."""
    global _recorder, _settings, _process_root
    with _rec_lock:
        _recorder = None
        _settings = None
        _process_root = None


def enabled() -> bool:
    return _load_settings()[0]


def _coin(sample: float) -> bool:
    """One sampling decision at ``HOROVOD_TRACE_SAMPLE``."""
    if sample >= 1.0:
        return True
    if sample <= 0.0:
        return False
    # secrets over random: no seed-correlation with user code
    return secrets.randbelow(1_000_000) < sample * 1_000_000


def mint(sampled: Optional[bool] = None) -> Optional[TraceContext]:
    """Mint a ROOT context, deciding sampling once for the whole trace.
    None when tracing is off or the coin came up tails — callers treat
    None as 'no tracing for this request' everywhere downstream."""
    on, sample = _load_settings()
    if not on:
        return None
    if sampled is None:
        sampled = _coin(sample)
    if not sampled:
        return None
    return TraceContext(_new_trace_id(), _new_span_id(), True)


def adopt(header: Optional[str]) -> Optional[TraceContext]:
    """Adopt an incoming traceparent header (or mint, when absent and
    tracing is on). The caller's sampling decision wins: an explicit
    sampled=0 header stays untraced."""
    if not enabled():
        return None
    ctx = parse_traceparent(header)
    if ctx is not None:
        return ctx if ctx.sampled else None
    return mint()


def start_span(
    name: str,
    parent: Optional[TraceContext],
    **tags,
) -> Optional[Span]:
    """Child span under ``parent``; None propagates (untraced request
    ⇒ no span, no cost). The returned span's ``.ctx`` is the context to
    hand the NEXT hop."""
    if parent is None or not parent.sampled:
        return None
    child = TraceContext(parent.trace_id, _new_span_id(), True)
    return Span(name, child, parent.span_id, tags)


# --------------------------------------------------------- process spans

_process_root: Optional[TraceContext] = None
_NO_SPAN = contextlib.nullcontext()


def process_root() -> TraceContext:
    """The context that process spans hang from when no span is active
    on their thread: minted once (``hvd.init`` does it; a span opened
    before init mints it then) and kept for the life of the process."""
    global _process_root
    root = _process_root
    if root is None:
        with _rec_lock:
            if _process_root is None:
                _process_root = TraceContext(
                    _new_trace_id(), _new_span_id(), True
                )
            root = _process_root
    return root


def span(name: str, **tags) -> Span:
    """A process span, ``with tracing.span("hvd.init.optimizer_init"): ...``:
    child of this thread's active span, else of the process root.
    Always recorded, so only for work that happens a bounded number of
    times a process; :func:`hot_span` is for the rest."""
    active_span = current()
    parent = active_span.ctx if active_span is not None else process_root()
    child = TraceContext(parent.trace_id, _new_span_id(), True)
    return Span(name, child, parent.span_id, tags)


def hot_span(name: str, **tags):
    """:func:`span` under ``HOROVOD_TRACE`` at ``HOROVOD_TRACE_SAMPLE``
    (a root's coin, thrown per span: a process span belongs to no
    request), else a shared null context (``as`` gives None): for spans
    per scheduler round or per idle stretch."""
    on, sample = _load_settings()
    return span(name, **tags) if on and _coin(sample) else _NO_SPAN


def trace_time_span(name: str, probe, **tags):
    """:func:`span` when ``probe`` is a JAX tracer, that is while
    ``jit``/``grad`` trace the caller, else a shared null context: an
    eager call pays one ``isinstance``. For code that runs on the host
    only at trace time (an optimizer's ``update``, a model's
    ``__call__``), whose Python is part of every cold start."""
    jax = sys.modules.get("jax")
    if jax is None or not isinstance(probe, jax.core.Tracer):
        return _NO_SPAN
    return span(name, **tags)


def record(name: str, start: float, end: float, **tags) -> None:
    """A process span for an interval that is already over (``start`` and
    ``end`` in ``time.time()`` seconds): a child of this thread's active
    span like :func:`span`, in the ring alone. For work whose start was
    not worth a live span (the compile ledger's events inside another)."""
    s = span(name, **tags)
    s.ts, s._t0 = start, time.monotonic() - (end - start)
    s.end()


def root_span(name: str, ctx: Optional[TraceContext], **tags):
    """The span a freshly-minted context BELONGS to (parent None) —
    the route/request root every leg hangs off. None propagates."""
    if ctx is None or not ctx.sampled:
        return None
    return Span(name, ctx, None, tags)


def server_stamps(peer_recv_ts: float) -> Dict[str, str]:
    """Headers a server echoes so the client can skew-correct this hop:
    its recv/send wall stamps and its process identity."""
    rec = recorder()
    return {
        TS_RECV_HEADER: f"{peer_recv_ts:.6f}",
        TS_SEND_HEADER: f"{time.time():.6f}",
        PEER_HEADER: f"{rec.host}:{rec.pid}",
    }


def json_stamps(peer_recv_ts: float) -> Dict[str, object]:
    """The :func:`server_stamps` echo for JSON-body protocols (the
    kv_transfer replies carry stamps as fields, not headers)."""
    rec = recorder()
    return {
        "recv_ts": round(peer_recv_ts, 6),
        "send_ts": round(time.time(), 6),
        "peer": f"{rec.host}:{rec.pid}",
    }


def tag_hop_fields(
    span: Optional[Span], t_send: float, t_recv: float, obj
) -> None:
    """:func:`tag_hop` for JSON-body echoes — the peer stamps arrive as
    ``recv_ts``/``send_ts``/``peer`` fields in the reply object."""
    if span is None or not isinstance(obj, dict):
        return
    peer_recv = obj.get("recv_ts")
    peer_send = obj.get("send_ts")
    if peer_recv is None or peer_send is None:
        return
    try:
        span.tag(
            t_send=round(t_send, 6),
            t_recv=round(t_recv, 6),
            peer_recv=round(float(peer_recv), 6),
            peer_send=round(float(peer_send), 6),
            peer=str(obj.get("peer", "")),
        )
    except (TypeError, ValueError):
        pass


def tag_hop(span: Optional[Span], t_send: float, t_recv: float, headers) -> None:
    """Tag the four NTP stamps + peer identity onto a client hop span
    from the server's echo headers (no-op on missing echo/span)."""
    if span is None or headers is None:
        return
    try:
        peer_recv = headers.get(TS_RECV_HEADER)
        peer_send = headers.get(TS_SEND_HEADER)
        peer = headers.get(PEER_HEADER)
    except AttributeError:
        return
    if not peer_recv or not peer_send:
        return
    try:
        span.tag(
            t_send=round(t_send, 6),
            t_recv=round(t_recv, 6),
            peer_recv=round(float(peer_recv), 6),
            peer_send=round(float(peer_send), 6),
            peer=peer or "",
        )
    except (TypeError, ValueError):
        pass
