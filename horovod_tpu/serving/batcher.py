"""Continuous batching: admissions between decode steps, never flushes.

The scheduler the Gemma-on-TPU serving paper centers on (PAPERS.md,
arXiv 2605.25645): a queued request is admitted into a freed decode
slot *between* decode steps — prefill it, write its KV rows, and the
next fixed-shape decode step simply carries one more live slot. No
retrace (shapes never change — engine.py), no flush (in-flight
sequences keep their slots and their cache), no batch barrier (a long
generation never holds short ones hostage, and vice versa).

Policy knobs:

* ``max_admit_per_step`` — prefills admitted between two decode steps
  (``HOROVOD_SERVE_MAX_BATCH``). Prefill happens on the decode thread,
  so each admission delays every in-flight token by one prefill: this
  knob IS the TTFT-vs-TPOT interleaving trade (docs/serving.md).
* ``policy="static"`` — the baseline continuous batching is compared
  with: admissions only when the previous batch fully completed, i.e.
  classic batched inference with its head-of-line blocking.
* per-request deadlines — queued requests expire before wasting a
  prefill; running requests are evicted at the deadline with their
  partial output (status ``"deadline"``).

Paged memory plane (serving/paged_kv.py, the default): admission is
additionally gated on free KV *pages* — a request is only admitted
when its worst-case prompt pages fit above the reserve watermark, so
mid-decode allocation can't strand in-flight sequences. If the pool
still exhausts mid-decode (prefix-cache churn, undersized pools), the
step does not raise: the YOUNGEST running request is paused — re-queued
at the front with its pages kept for a pointer-cheap resume — and, as
the last resort, paused requests' kept pages are reclaimed
deadline-aware (nearest deadline first; those resume by re-prefilling
prompt + generated-so-far, usually through the prefix cache).

Draining (``drain()``, wired to SIGTERM via
``preemption.register_drain``) stops ADMISSION of new submissions but
runs queue + in-flight to completion — every accepted request finishes
before the worker leaves the gang. With a drain DEADLINE
(``HOROVOD_SERVE_DRAIN_DEADLINE_S``), sequences still in flight past
it are live-migrated instead: :meth:`export_inflight` detaches each
slot's pages + generated tokens + armed sampling state and the
frontend streams them to a reserved peer over the kv_transfer wire
(the ``migrate`` frame), where they resume mid-decode without
re-prefill.

Role-split fleets (``HOROVOD_SERVE_ROLE``, serving/kv_transfer.py): a
``prefill``-role batcher reserves decode capacity BEFORE each fresh
prefill, then detaches the finished pages and hands them to the
transfer coordinator — the request never occupies a decode slot here
unless the transfer plane has no capacity (local fallback, the
unified path). A ``decode``-role batcher admits transferred requests
through :meth:`submit_ingested`: the foreign pages pointer-attach
exactly like a pause-resume, so admission changes data, never shapes —
``decode_compiles`` stays 1. In-flight handoffs count against drain:
SIGTERM waits for streamed requests to finish or fall back.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..common import telemetry as _telemetry
from ..common import tracing as _tracing
from ..common.logging import get_logger
from ..common.metrics import registry as _metrics
from ..testing import chaos as _chaos
from .paged_kv import PagePoolExhausted
from .slo import LatencyRecorder

_log = get_logger("serve.batcher")

QUEUED = "queued"
RUNNING = "running"
DONE = "done"
DEADLINE = "deadline"
REJECTED = "rejected"
ERROR = "error"


class Rejected(RuntimeError):
    """Request refused at submission (draining, or it can never fit)."""


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray
    max_new_tokens: int
    deadline_ts: Optional[float]  # monotonic; None = no deadline
    submitted: float = dataclasses.field(default_factory=time.monotonic)
    status: str = QUEUED
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    ttft_ms: float = 0.0
    gen_ms: float = 0.0
    # one stamp per output token, ms from submission to the moment the
    # batcher committed it (:meth:`commit`): what a client needs to time
    # the first token and every gap, since the reply comes only when
    # the request is done
    token_ms: List[float] = dataclasses.field(default_factory=list)
    # ms from submission to the first admission (a slot and its pages
    # were there): the queue wait, which TTFT holds together with the
    # prefill
    queue_ms: float = 0.0
    # paged memory plane (serving/paged_kv.py): pause/resume state. A
    # request paused on pool exhaustion re-queues with ``paused=True``;
    # ``kept_pages`` holds its page-table snapshot (refcounts
    # transferred from the slot) so resume is a pointer re-attach — or
    # None once the deadline-aware reclaim dropped them, in which case
    # resume re-prefills prompt + generated-so-far.
    paused: bool = False
    kept_pages: Optional[list] = None
    resume_length: int = 0
    admit_seq: int = -1
    # KV-transfer ingest payload (serving/kv_transfer.py): host page
    # arrays + logical indices waiting for their admit-time device
    # write. Dropped (None) once attached — the arrays are large.
    ingest: Optional[dict] = dataclasses.field(default=None, repr=False)
    # per-request sampling (engine.set_sampling — pure DATA through the
    # one decode executable): temperature 0 = bit-identical greedy,
    # top_k 0 = no truncation, seed None = derived from the request id
    # (stable across replays). Armed at every admission (fresh, resume
    # and ingest alike), cleared when the slot retires.
    temperature: float = 0.0
    top_k: int = 0
    seed: Optional[int] = None
    # trace plane (common/tracing.py): the request's TraceContext (None
    # = untraced — every span site below skips on None, so the default
    # path carries zero tracing cost) and the open admit→retire decode
    # span riding it
    trace: Optional[object] = dataclasses.field(default=None, repr=False)
    span: Optional[object] = dataclasses.field(default=None, repr=False)
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )

    def commit(self, token: int, now: Optional[float] = None) -> None:
        """Append one output token with its stamp (``now``: a
        ``time.monotonic()`` reading, taken here when not given)."""
        now = time.monotonic() if now is None else now
        self.out_tokens.append(int(token))
        self.token_ms.append((now - self.submitted) * 1e3)

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def finished(self) -> bool:
        return self._done.is_set()

    def result(self) -> Dict:
        out = {
            "id": self.id,
            "status": self.status,
            "tokens": list(self.out_tokens),
            "prompt_len": int(self.prompt.size),
            "ttft_ms": round(self.ttft_ms, 3),
            "gen_ms": round(self.gen_ms, 3),
            "token_ms": [round(t, 3) for t in self.token_ms],
            "queue_ms": round(self.queue_ms, 3),
        }
        if self.trace is not None:
            out["trace_id"] = self.trace.trace_id
        return out


class ContinuousBatcher:
    """Single decode-thread scheduler over an InferenceEngine."""

    def __init__(
        self,
        engine,
        *,
        max_admit_per_step: int = 4,
        default_max_new_tokens: int = 64,
        default_deadline_ms: float = 0.0,
        eos_id: Optional[int] = None,
        policy: str = "continuous",
        recorder: Optional[LatencyRecorder] = None,
        role: str = "unified",
    ) -> None:
        if policy not in ("continuous", "static"):
            raise ValueError(f"unknown policy {policy!r}")
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"unknown serve role {role!r}")
        if role != "unified" and not engine.paged:
            raise ValueError(
                "prefill/decode roles need the paged KV plane "
                "(HOROVOD_SERVE_KV=paged) — the transfer wire moves "
                "pool pages, not slab slots"
            )
        self.engine = engine
        self.role = role
        # TransferCoordinator (prefill role), wired by serve() after
        # construction — None means no transfer plane: every request
        # decodes locally (the unified path)
        self.transfer = None
        self._handoffs = 0  # requests streamed out, result not back yet
        self.max_admit_per_step = max(int(max_admit_per_step), 1)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.default_deadline_ms = float(default_deadline_ms)
        self.eos_id = eos_id
        self.policy = policy
        self.recorder = recorder or LatencyRecorder()
        self._ids = itertools.count()
        self._admit_ids = itertools.count()
        self._cond = threading.Condition()
        self._queue: "deque[Request]" = deque()
        self._slot_req: Dict[int, Request] = {}
        self._draining = False
        self._drain_active = False  # a drain() loop is live-stepping
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._decode_steps = 0
        self._last_publish = 0.0

    # ------------------------------------------------------------ submission

    def submit(
        self,
        prompt,
        max_new_tokens: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: Optional[int] = None,
        trace=None,
    ) -> Request:
        if self.role == "decode":
            # the Router never sends prompts here (role-aware pick);
            # this guard keeps a misconfigured client from tripping the
            # engine's role gate deep inside the scheduler thread
            _metrics.counter("serve.rejected")
            raise Rejected(
                "decode-role worker takes KV transfers, not prompts"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            _metrics.counter("serve.rejected")
            raise Rejected("empty prompt")
        max_new = (
            self.default_max_new_tokens
            if max_new_tokens is None
            else int(max_new_tokens)
        )
        # the generation must fit the slot's KV capacity: clamp, and
        # reject prompts that leave no room for even the first token
        max_new = min(max_new, self.engine.max_len - int(prompt.size))
        if max_new < 1:
            _metrics.counter("serve.rejected")
            raise Rejected(
                f"prompt of {prompt.size} tokens leaves no room in a "
                f"{self.engine.max_len}-token KV slot"
            )
        if self.engine.paged:
            mgr = self.engine.manager
            worst = mgr.pages_needed(int(prompt.size) + max_new)
            if worst > mgr.num_pages:
                # can NEVER fit, even with the whole pool to itself —
                # the paged analog of the slot-capacity reject above
                _metrics.counter("serve.rejected")
                raise Rejected(
                    f"request needs {worst} KV pages but the pool has "
                    f"only {mgr.num_pages}"
                )
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        req = Request(
            id=next(self._ids),
            prompt=prompt,
            max_new_tokens=max_new,
            deadline_ts=(
                time.monotonic() + deadline_ms / 1e3
                if deadline_ms and deadline_ms > 0
                else None
            ),
            temperature=float(temperature),
            top_k=int(top_k),
            seed=seed,
            trace=trace,
        )
        with self._cond:
            # drain check and enqueue under ONE lock: a submit racing
            # the SIGTERM drain either lands before the flag flips (the
            # drain loop re-checks the queue, so it WILL be served) or
            # sees the flag and is rejected — never accepted-then-lost
            if self._draining:
                _metrics.counter("serve.rejected")
                raise Rejected(
                    "worker is draining (shutdown in progress)"
                )
            self._queue.append(req)
            self._cond.notify_all()
        _metrics.counter("serve.requests_total")
        self._publish_gauges()
        return req

    # ------------------------------------------------- transfer plane hooks

    def submit_ingested(
        self,
        prompt,
        first_token: int,
        max_new_tokens: int,
        logical,
        arrays,
        length: int,
        hashes=(),
        deadline_ms: Optional[float] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: Optional[int] = None,
        trace=None,
    ) -> Request:
        """Admit a KV-transferred request (serving/kv_transfer.py
        receiver). Called from an HTTP handler thread: only host-side
        bookkeeping happens here — the device write (ingest_attach)
        runs at admit time on the scheduler thread, like every other
        pool touch. The first token was already emitted by the remote
        prefill, so ``out_tokens`` starts seeded and decode produces
        the remaining ``max_new_tokens - 1``."""
        if not self.engine.paged:
            raise Rejected("KV ingest needs the paged plane")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pages = list(logical)
        if len(pages) > self.engine.manager.num_pages:
            _metrics.counter("serve.rejected")
            raise Rejected(
                f"ingest of {len(pages)} pages exceeds the "
                f"{self.engine.manager.num_pages}-page pool"
            )
        req = Request(
            id=next(self._ids),
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            deadline_ts=(
                time.monotonic() + float(deadline_ms) / 1e3
                if deadline_ms and float(deadline_ms) > 0
                else None
            ),
            temperature=float(temperature),
            top_k=int(top_k),
            seed=seed,
            trace=trace,
        )
        req.commit(first_token)
        req.ingest = {
            "logical": [int(lp) for lp in pages],
            "arrays": arrays,
            "length": int(length),
            "hashes": list(hashes),
        }
        with self._cond:
            if self._draining:
                _metrics.counter("serve.rejected")
                raise Rejected("worker is draining (shutdown in progress)")
            self._queue.append(req)
            self._cond.notify_all()
        _metrics.counter("serve.requests_total")
        self._publish_gauges()
        return req

    def submit_migrated(
        self,
        prompt,
        tokens,
        max_new_tokens: int,
        logical,
        arrays,
        length: int,
        deadline_ms: Optional[float] = None,
        sample: Optional[dict] = None,
        trace=None,
    ) -> Request:
        """Admit a live-migrated in-flight sequence (the ``migrate``
        frame, serving/kv_transfer.py receiver). Unlike
        :meth:`submit_ingested` the request arrives MID-DECODE: the
        full generated-token history seeds ``out_tokens`` (the newest
        one feeds the next decode step — the same frontier it left the
        sender at) and ``sample`` carries the sender's armed sampling
        snapshot including the raw mid-stream PRNG key, so sampled
        sequences continue bit-identically. No prefix publication: the
        pages hold generated tokens, not a shareable prompt prefix."""
        if not self.engine.paged:
            raise Rejected("migration needs the paged plane")
        toks = [int(t) for t in tokens]
        if not toks:
            raise Rejected("migrated sequence carries no tokens")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pages = list(logical)
        if len(pages) > self.engine.manager.num_pages:
            _metrics.counter("serve.rejected")
            raise Rejected(
                f"migration of {len(pages)} pages exceeds the "
                f"{self.engine.manager.num_pages}-page pool"
            )
        req = Request(
            id=next(self._ids),
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            deadline_ts=(
                time.monotonic() + float(deadline_ms) / 1e3
                if deadline_ms and float(deadline_ms) > 0
                else None
            ),
            trace=trace,
        )
        for tok in toks:
            req.commit(tok)
        req.ingest = {
            "logical": [int(lp) for lp in pages],
            "arrays": arrays,
            "length": int(length),
            "hashes": [],
            "sample": sample,
        }
        with self._cond:
            if self._draining:
                _metrics.counter("serve.rejected")
                raise Rejected("worker is draining (shutdown in progress)")
            self._queue.append(req)
            self._cond.notify_all()
        _metrics.counter("serve.requests_total")
        self._publish_gauges()
        return req

    def requeue_fallback(self, req: Request, kept, length: int) -> None:
        """Transfer failed after the prefill (retries exhausted, or the
        decode worker answered with an error status): bring the request
        home. Its pages are still held, so it re-queues paused at the
        FRONT for a pointer-cheap local decode — even while draining
        (it was accepted; accepted work completes). Called from the
        handoff thread."""
        req.kept_pages = kept
        req.resume_length = int(length)
        req.paused = True
        req.status = QUEUED
        with self._cond:
            self._handoffs -= 1
            if (
                self._draining and not self._drain_active
                and not self._running and self._thread is None
            ):
                # scheduler crashed or already stopped: nothing will
                # ever serve the queue — fail loudly, don't park waiters
                req.kept_pages = None
                self.engine.manager.release_kept(kept)
                req.status = ERROR
                req._done.set()
                _metrics.counter("serve.errored")
                return
            self._queue.appendleft(req)
            self._cond.notify_all()
        _metrics.counter("serve.transfer_fallbacks")
        _log.info(
            "request %d fell back to local decode after transfer failure",
            req.id,
        )

    def complete_handoff(self, req: Request, result: Dict) -> None:
        """Remote decode finished: copy the decode worker's output into
        the local request and release its waiter. TTFT stays the value
        measured HERE (the client's clock); gen_ms is the decode
        worker's. Called from the handoff thread."""
        req.out_tokens = [int(t) for t in result.get("tokens", ())]
        req.gen_ms = float(result.get("gen_ms", 0.0))
        # the decode worker's stamps count from ITS submission, which
        # followed this worker's first token: shifted by the local
        # TTFT they leave out the transfer, like gen_ms does
        remote = [float(t) for t in result.get("token_ms", ())]
        req.token_ms = req.token_ms[:1] + [
            req.ttft_ms + t for t in remote[1:]
        ]
        req.status = DONE if result.get("status") == "done" else DEADLINE
        with self._cond:
            self._handoffs -= 1
            self._cond.notify_all()
        if req.status == DONE:
            _metrics.counter("serve.completed")
        else:
            _metrics.counter("serve.expired")
        _metrics.counter("serve.handed_off")
        req._done.set()

    # ------------------------------------------------------------- the loop

    def start(self) -> None:
        if self._thread is not None:
            return
        self._running = True
        self._thread = threading.Thread(
            target=self._run, name="hvd-serve-batcher", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def drain(
        self,
        timeout: float = 30.0,
        migrate_after: Optional[float] = None,
        on_deadline=None,
    ) -> bool:
        """Stop admitting NEW submissions; run everything already
        accepted (queued + in-flight) to completion. Returns True when
        the plane is empty. Works both loop-driven and manually-stepped
        (tests): without a running loop the drain steps inline.

        With ``migrate_after`` (seconds) AND an ``on_deadline``
        callback, sequences still in flight past that point are
        exported (:meth:`export_inflight`) and handed to the callback —
        the frontend's live-migration hook. The exported records count
        as handoffs, so the drain keeps waiting until each one's result
        lands (remote completion) or its fallback requeue is served
        inline."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        start = time.monotonic()
        deadline = start + timeout
        migrate_at = (
            start + max(float(migrate_after), 0.0)
            if migrate_after is not None and on_deadline is not None
            else None
        )
        self._drain_active = True
        try:
            while time.monotonic() < deadline:
                if (
                    not self._queue and not self._slot_req
                    and not self._handoffs
                ):
                    return True
                if (
                    migrate_at is not None
                    and time.monotonic() >= migrate_at
                ):
                    migrate_at = None
                    if self._slot_req:
                        records = self.export_inflight()
                        _log.info(
                            "drain deadline: migrating %d in-flight "
                            "sequence(s)", len(records),
                        )
                        on_deadline(records)
                    continue
                if self._running:
                    time.sleep(0.005)
                elif not self.step():
                    # idle but handoffs still in flight: they finish
                    # (or fall back into the queue) on their own threads
                    time.sleep(0.005)
            return (
                not self._queue and not self._slot_req
                and not self._handoffs
            )
        finally:
            self._drain_active = False

    def export_inflight(self) -> List[dict]:
        """Detach every in-flight sequence for live migration (the
        drain-deadline path). Stops the scheduler loop first — the
        drain thread becomes the single consumer — then, per slot:
        snapshot the armed sampling state BEFORE the detach (the raw
        mid-stream PRNG key; clearing after detach keeps the next
        occupant clean), detach the pages with refcounts transferred,
        and count the record as an in-flight handoff so drain() waits
        for its remote result or fallback exactly like a streamed
        prefill."""
        self.stop()
        records: List[dict] = []
        for slot in sorted(self._slot_req):
            req = self._slot_req.pop(slot)
            sample = self.engine.export_sampling(slot)
            kept, length = self.engine.manager.detach_keep(slot)
            self.engine.clear_sampling(slot)
            records.append({
                "req": req,
                "kept": kept,
                "length": length,
                "sample": sample,
            })
        with self._cond:
            self._handoffs += len(records)
        self._publish_gauges(min_interval=0.0)
        return records

    def _run(self) -> None:
        # holds the open ``hvd.batcher.idle_wait`` span: one per idle
        # stretch (HOROVOD_TRACE), however many 20 ms waits it takes
        with contextlib.ExitStack() as idle:
            self._rounds(idle)

    def _rounds(self, idle: contextlib.ExitStack) -> None:
        waiting = False
        while True:
            with self._cond:
                if not self._running:
                    return
                if waiting and self._queue:
                    idle.close()  # work arrived: the stretch ends here
                    waiting = False
            try:
                did = self.step()
            except Exception:
                # the scheduler thread must NEVER die silently: every
                # accepted request's done-event would stay unset and
                # the HTTP handlers parked on them would block forever
                # while the announce loop kept advertising a live
                # worker. Fail loudly: abort everything accepted,
                # refuse new work, and let /healthz report not-ok.
                _log.exception(
                    "serve scheduler failed; aborting accepted requests"
                )
                self._abort_all("scheduler failure")
                with self._cond:
                    self._draining = True
                    self._running = False
                return
            if did and waiting:  # work that came past the queue
                idle.close()
                waiting = False
            if not did:
                with self._cond:
                    if self._running and not self._queue:
                        # short timeout: queued deadlines must still
                        # expire while the plane idles
                        if not waiting:
                            idle.enter_context(_tracing.hot_span(
                                "hvd.batcher.idle_wait",
                                active=len(self._slot_req),
                            ))
                            waiting = True
                        self._cond.wait(timeout=0.02)

    def _abort_all(self, reason: str) -> None:
        """Fail every queued and in-flight request (status ``error``)
        so their waiters unblock — the crash path's drain."""
        with self._cond:
            queued = list(self._queue)
            self._queue.clear()
        for slot in list(self._slot_req):
            req = self._slot_req.pop(slot)
            self.engine.manager.free(slot)
            queued.append(req)
        for req in queued:
            if req.kept_pages:
                self.engine.manager.release_kept(req.kept_pages)
                req.kept_pages = None
            req.status = ERROR
            if req.span is not None:
                req.span.end(outcome="error", reason=reason)
                req.span = None
            req._done.set()
            _metrics.counter("serve.errored")
        self._publish_gauges(min_interval=0.0)

    # ------------------------------------------------------------- one step

    def step(self) -> bool:
        """One scheduler round: expire → admit → decode → retire.
        Returns False when there was nothing to do (idle)."""
        # chaos site `serve.worker_kill`: a transport-kind fault raises
        # here — the loop's crash handler aborts every accepted request
        # (the Router's replay path fires); the `kill` kind SIGKILLs
        # the process for the subprocess drills
        _chaos.inject("serve.worker_kill")
        now = time.monotonic()
        self._expire_queued(now)
        admitted = self._admit(now)
        stepped = self._decode(now)
        self._publish_gauges()
        return bool(admitted or stepped)

    def _expire_queued(self, now: float) -> None:
        with self._cond:
            keep: "deque[Request]" = deque()
            expired = []
            for req in self._queue:
                if req.deadline_ts is not None and now >= req.deadline_ts:
                    expired.append(req)
                else:
                    keep.append(req)
            self._queue = keep
        for req in expired:
            if req.kept_pages:
                # a paused request expiring in the queue releases the
                # pages it was holding for resume
                self.engine.manager.release_kept(req.kept_pages)
                req.kept_pages = None
            req.status = DEADLINE
            req._done.set()
            _metrics.counter("serve.expired")

    def _resume_seq(self, req: Request) -> np.ndarray:
        """The token sequence a page-dropped paused request re-prefills:
        prompt plus everything generated EXCEPT the newest token — that
        one is fed to the next decode step (which writes its kv), the
        same frontier the request was paused at."""
        return np.concatenate(
            [req.prompt, np.asarray(req.out_tokens[:-1], np.int32)]
        )

    def _admission_pages_needed(self, req: Request) -> int:
        """Pages the admission gate must see headroom for: a resume
        with kept pages needs none (they are already held); a
        page-dropped resume re-prefills its whole sequence-so-far; a
        fresh request needs its prompt (prefix hits can only reduce
        this — the gate is deliberately conservative)."""
        mgr = self.engine.manager
        if req.kept_pages is not None:
            return 0
        if req.ingest is not None:
            return len(req.ingest["logical"])
        if req.paused and req.out_tokens:
            return mgr.pages_needed(self._resume_seq(req).size)
        return mgr.pages_needed(int(req.prompt.size))

    def _admit(self, now: float) -> int:
        admitted = 0
        mid_decode = bool(self._slot_req)
        if self.policy == "static" and mid_decode:
            return 0
        limit = (
            self.engine.slots
            if self.policy == "static"
            else self.max_admit_per_step
        )
        paged = self.engine.paged
        while admitted < limit:
            sample_armed = False
            with self._cond:
                if not self._queue:
                    break
                req = self._queue[0]
            if paged and (
                self._admission_pages_needed(req)
                > self.engine.manager.admission_headroom()
            ):
                # the page gate: admission never dips into the reserve
                # watermark — those pages belong to in-flight decodes
                break
            slot = self.engine.manager.alloc(req.id)
            if slot is None:
                break
            with self._cond:
                # single consumer: the head is still req
                self._queue.popleft()
            if req.admit_seq < 0:
                req.queue_ms = (time.monotonic() - req.submitted) * 1e3
            req.admit_seq = next(self._admit_ids)
            if req.kept_pages is not None:
                # resume from pause: the kept pages pointer-attach and
                # decode continues exactly where it stopped — no
                # prefill, no second TTFT
                self.engine.manager.reattach(
                    slot, req.kept_pages, req.resume_length
                )
                req.kept_pages = None
                req.paused = False
                req.status = RUNNING
                _metrics.counter("serve.resumed")
                if req.trace is not None:
                    s = _tracing.start_span(
                        "serve.resume", req.trace, path="reattach",
                        slot=slot,
                    )
                    if s is not None:
                        s.end()
            elif req.ingest is not None:
                # KV-transfer ingest: foreign pages land in the pool
                # and pointer-attach — data changes, shapes don't, so
                # this admission path never retraces (decode_compiles
                # stays 1 across streamed admissions)
                ing = req.ingest
                kept = self.engine.ingest_attach(
                    slot, ing["logical"], ing["arrays"],
                    ing["length"], ing["hashes"],
                )
                if kept is None:
                    # pool raced dry between the gate and the alloc
                    # (reserve TTL expiry, prefix churn): put the head
                    # back and stop admitting this round
                    self.engine.manager.free(slot)
                    with self._cond:
                        self._queue.appendleft(req)
                    break
                if ing.get("sample"):
                    # migrated resume: import the sender's armed
                    # sampling snapshot (raw mid-stream key) verbatim —
                    # the common arming block below would re-seed and
                    # fork the sampled sequence
                    self.engine.import_sampling(slot, ing["sample"])
                    sample_armed = True
                npages = len(ing["logical"])
                req.ingest = None
                req.status = RUNNING
                _metrics.counter("serve.transfer_admits")
                _metrics.counter("serve.tokens_out")
                if req.trace is not None:
                    s = _tracing.start_span(
                        "serve.ingest_admit", req.trace, pages=npages,
                        slot=slot, migrated=bool(sample_armed),
                    )
                    if s is not None:
                        s.end()
            else:
                if req.paused and req.out_tokens:
                    # pages were reclaimed while paused: rebuild the
                    # slot by re-prefilling prompt + generated-so-far
                    # (the prefix cache usually makes this cheap); the
                    # emitted token is discarded — the real newest
                    # token is fed to the next decode step
                    pspan = _tracing.start_span(
                        "serve.prefill", req.trace, resume=True,
                        slot=slot,
                    )
                    self.engine.prefill(
                        slot, self._resume_seq(req), trace=req.trace
                    )
                    if pspan is not None:
                        pspan.end()
                    req.paused = False
                    req.status = RUNNING
                    _metrics.counter("serve.resumed")
                else:
                    reservation = None
                    if (
                        self.role == "prefill"
                        and self.transfer is not None
                        and req.max_new_tokens > 1
                    ):
                        # reserve decode capacity BEFORE spending the
                        # prefill — a prefill whose pages have nowhere
                        # to go is work wasted (docs/serving.md
                        # reservation protocol)
                        need = self.engine.manager.pages_needed(
                            int(req.prompt.size) + req.max_new_tokens
                        )
                        reservation = self.transfer.reserve(
                            need, trace=req.trace
                        )
                        if reservation is None:
                            # no decode capacity anywhere: the unified
                            # path — decode locally (this role compiles
                            # its decode table lazily, only here)
                            _metrics.counter("serve.transfer_local")
                    pspan = _tracing.start_span(
                        "serve.prefill", req.trace,
                        prompt_len=int(req.prompt.size), slot=slot,
                    )
                    first = self.engine.prefill(
                        slot, req.prompt, trace=req.trace
                    )
                    req.status = RUNNING
                    t_first = time.monotonic()
                    req.ttft_ms = (t_first - req.submitted) * 1e3
                    req.commit(first, t_first)
                    if pspan is not None:
                        pspan.end(ttft_ms=round(req.ttft_ms, 3))
                    self.recorder.record_ttft(
                        req.ttft_ms,
                        req.trace.trace_id if req.trace else "",
                    )
                    _metrics.counter(
                        "serve.prefill_tokens", int(req.prompt.size)
                    )
                    _metrics.counter("serve.tokens_out")
                    if reservation is not None:
                        # hand the finished pages to the transfer
                        # coordinator: detach_keep frees the slot (the
                        # refcounts move to the handoff), the stream +
                        # result-wait run off-thread, and this worker's
                        # decode plane never sees the request
                        kept, length = self.engine.manager.detach_keep(
                            slot
                        )
                        with self._cond:
                            self._handoffs += 1
                        self.transfer.start_handoff(
                            self, req, kept, length, reservation
                        )
                        admitted += 1
                        continue
            if mid_decode:
                # counted for every admission path — fresh prefill,
                # reprefill-resume AND pointer reattach-resume alike
                _metrics.counter("serve.admitted_mid_decode")
            admitted += 1
            # arm the slot's sampling knobs for every admission path
            # (fresh, resume, ingest): data writes, never a retrace —
            # except a migrated resume, whose imported key already IS
            # the armed state
            if sample_armed:
                pass
            elif req.temperature > 0 or req.top_k > 0:
                self.engine.set_sampling(
                    slot, req.temperature, req.top_k,
                    seed=req.id if req.seed is None else req.seed,
                )
            else:
                self.engine.clear_sampling(slot)
            if req.trace is not None and req.span is None:
                # admit→retire lifecycle span: opened ONCE (survives
                # pause/resume cycles), closed by _retire/_abort_all —
                # no per-decode-step tracing work happens inside it
                req.span = _tracing.start_span(
                    "serve.decode", req.trace, slot=slot,
                )
            self._slot_req[slot] = req
            if self._req_complete(req, now):
                self._retire(slot, req)
        return admitted

    def _pause_youngest(self, now: float) -> bool:
        """Pool-exhaustion remedy: take the youngest running request
        out of its slot and re-queue it (front), keeping its pages for
        a pointer-cheap resume. A request already past its deadline
        expires instead (its pages free immediately). Returns False
        when there is no second request to pause."""
        if len(self._slot_req) < 2:
            return False
        slot, req = max(
            self._slot_req.items(), key=lambda kv: kv[1].admit_seq
        )
        self._slot_req.pop(slot)
        mgr = self.engine.manager
        if req.deadline_ts is not None and now >= req.deadline_ts:
            mgr.free(slot)
            req.status = DEADLINE
            req._done.set()
            _metrics.counter("serve.expired")
            return True
        req.kept_pages, req.resume_length = mgr.detach_keep(slot)
        req.paused = True
        req.status = QUEUED
        with self._cond:
            self._queue.appendleft(req)
        _metrics.counter("serve.paused")
        if req.trace is not None:
            s = _tracing.start_span(
                "serve.pause", req.trace, slot=slot,
                kept_pages=len(req.kept_pages),
            )
            if s is not None:
                s.end()
        _log.debug(
            "page pool exhausted: paused request %d (kept %d pages)",
            req.id, len(req.kept_pages),
        )
        return True

    def _reclaim_paused_pages(self) -> bool:
        """Last-resort page source: drop the kept pages of a paused
        request so an older in-flight one can take its next page.
        Deadline-aware: the victim is the paused holder with the LEAST
        deadline headroom (most likely to expire unserved anyway);
        holders with no deadline are spared longest. The victim stays
        queued — it re-prefills on resume."""
        if self.role == "decode":
            # a decode-role worker has no prefill executables: dropped
            # pages could never be rebuilt, so kept holds are pinned —
            # pause (pointer resume) remains the only remedy here
            return False
        with self._cond:
            holders = [r for r in self._queue if r.kept_pages]
        if not holders:
            return False
        victim = min(
            holders,
            key=lambda r: (
                r.deadline_ts is None,
                r.deadline_ts if r.deadline_ts is not None else 0.0,
            ),
        )
        self.engine.manager.release_kept(victim.kept_pages)
        victim.kept_pages = None
        _metrics.counter("serve.paused_pages_reclaimed")
        return True

    def _make_decodable(self, now: float) -> None:
        """Run the pre-decode page sweep until every remaining slot
        can take its next token, pausing the youngest request (then
        reclaiming paused holds) as needed — graceful degradation, the
        step itself never sees exhaustion."""
        # bounded: each round pauses a request or reclaims one holder
        for _ in range(self.engine.slots + len(self._queue) + 2):
            if not self.engine.prepare_decode():
                return
            if self._pause_youngest(now):
                continue
            if self._reclaim_paused_pages():
                continue
            # a single in-flight request, nothing left to reclaim:
            # unreachable when the pool admits only what fits
            # (submit's can-never-fit gate), but never silent
            raise PagePoolExhausted(
                list(self.engine.prepare_decode())
            )

    def _decode(self, now: float) -> bool:
        if not self._slot_req:
            return False
        if self.engine.paged:
            self._make_decodable(now)
            if not self._slot_req:
                return False
        tokens = np.zeros(self.engine.slots, np.int32)
        for slot, req in self._slot_req.items():
            tokens[slot] = req.out_tokens[-1]
        hub = None
        if _telemetry.auto_enabled():
            hub = _telemetry.hub()
            hub.step_begin(self._decode_steps)
        t0 = time.monotonic()
        nxt = self.engine.decode_step(tokens)
        step_ms = (time.monotonic() - t0) * 1e3
        self._decode_steps += 1
        now = time.monotonic()
        for slot, req in list(self._slot_req.items()):
            self.engine.manager.advance(slot)
            req.commit(nxt[slot], now)
            req.gen_ms = (now - req.submitted) * 1e3 - req.ttft_ms
            self.recorder.record_tpot(
                step_ms, req.trace.trace_id if req.trace else ""
            )
            _metrics.counter("serve.tokens_out")
            if self._req_complete(req, now):
                self._retire(slot, req)
        if hub is not None:
            # close AFTER the per-token bookkeeping so the record's
            # serve.* deltas carry this step's tokens
            hub.step_end()
        return True

    def _req_complete(self, req: Request, now: float) -> bool:
        if req.deadline_ts is not None and now >= req.deadline_ts:
            req.status = DEADLINE
            return True
        if len(req.out_tokens) >= req.max_new_tokens:
            return True
        if self.eos_id is not None and req.out_tokens[-1] == self.eos_id:
            return True
        return False

    def _retire(self, slot: int, req: Request) -> None:
        self.engine.manager.free(slot)
        self.engine.clear_sampling(slot)
        self._slot_req.pop(slot, None)
        if req.status == DEADLINE:
            _metrics.counter("serve.expired")
        else:
            req.status = DONE
            _metrics.counter("serve.completed")
        if req.span is not None:
            req.span.end(
                outcome=req.status, tokens=len(req.out_tokens),
                steps=self._decode_steps,
            )
            req.span = None
        req._done.set()

    # --------------------------------------------------------------- stats

    @property
    def draining(self) -> bool:
        """True once no new work is accepted — set by drain() or by the
        scheduler-crash handler. The frontend folds this into its own
        draining state (503s, /healthz, the KV announcement), so a
        crashed batcher is visibly drained fleet-wide, not a 429-ing
        blackhole the Router keeps preferring."""
        return self._draining

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    def active(self) -> int:
        return len(self._slot_req)

    def stats(self) -> Dict[str, float]:
        out = {
            "queue_depth": self.queue_depth(),
            "decode_steps": self._decode_steps,
            "draining": 1.0 if self._draining else 0.0,
            "handoffs_inflight": float(self._handoffs),
        }
        out.update(self.engine.manager.stats())
        return out

    def _publish_gauges(self, min_interval: float = 0.25) -> None:
        """Registry gauge refresh, rate-limited off the decode hot path
        (recorder.publish sorts the latency rings — O(capacity log
        capacity) per call has no business running per token; the serve
        port's /metrics renders its summaries live regardless, so only
        scrape-side registry staleness is bounded by the interval)."""
        now = time.monotonic()
        if now - self._last_publish < min_interval:
            return
        self._last_publish = now
        _metrics.update("serve", self.stats())
        self.engine.publish()
        self.recorder.publish()
