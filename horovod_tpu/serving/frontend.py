"""HTTP frontend + fleet routing: ``hvd.serve(model, params, port=…)``.

The MetricsServer mold (common/telemetry.py): a stdlib
``ThreadingHTTPServer`` per worker, no new dependencies.

Routes:

* ``POST /generate`` — body ``{"tokens": [...], "max_tokens"?,
  "deadline_ms"?, "temperature"?, "top_k"?, "seed"?}``; blocks until
  the request completes (the handler
  thread parks on the request's event; the batcher's decode thread
  does the work) and replies the result JSON (tokens, status, TTFT,
  generation wall). 503 while draining; 429 when rejected.
* ``GET /healthz`` — liveness + capacity JSON (free slots, queue
  depth): the router's direct probe and the LB health check.
* ``GET /metrics`` — the registry render (common/telemetry.py) with
  the TTFT/TPOT families as real Prometheus summaries prepended, so a
  fleet scraper needs only this one port per worker.
* ``GET /stats`` — engine + batcher counters as JSON.

**Fleet plane:** each worker announces ``{rank, addr, port, free_slots,
queue_depth, ts}`` — plus, under the paged memory plane,
``free_pages`` / ``pages_total`` / ``prefix_hit_rate`` — into the
rendezvous KV (scope ``serve``) on a timer — the same channel
heartbeats ride. ``Router`` reads those announcements plus the
heartbeat straggler ledger (``runner.rendezvous.read_heartbeat_stats``
→ ``StallInspector.straggler_ranks``) and directs each request to the
least-loaded worker whose rank is NOT flagged — the PR 4 ledger driving
traffic, not just logs. Page headroom outranks slot headroom when both
are announced (pages are what admission actually gates on); old
``free_slots``-only blobs keep parsing, so mixed fleets mid-rollout
stay routable.

**Disaggregated fleets** (``HOROVOD_SERVE_ROLE``, docs/serving.md):
announcements carry ``role`` and — on decode workers — the
``transfer_port`` of the KV-ingest endpoint (serving/kv_transfer.py).
The Router sends ``/generate`` traffic to PREFILL workers when any
exist (unified workers otherwise) and NEVER to decode workers — their
requests arrive as streamed KV pages, not prompts. Blobs with no
``role`` field at all (old workers mid-rollout) parse as ``unified``
and stay routable.

**Drain:** ``serve()`` registers the frontend's drain with
``preemption.register_drain``, so a SIGTERM under ``GracefulShutdown``
(or the handler ``serve()`` installs itself) finishes every accepted
request, lets the in-flight HTTP responses flush, and only then lets
the worker leave the gang. With ``HOROVOD_SERVE_DRAIN_DEADLINE_S``
set, sequences still in flight past the deadline are LIVE-MIGRATED to
a reserved peer over the kv_transfer wire instead of run to completion
— the preemption grace window is honored without dropping a request.

**Crash-safe routing** (docs/robustness.md "serving failure model"):
the Router keeps each request's full submission (it IS the journal —
prompt, sampling knobs, client request_id) and, when a worker dies
mid-call, transparently REPLAYS it on a live worker
(``serve.replays``), tombstoning the dead worker's announcement for
one freshness period so the stale blob can't re-attract the next
request. Workers dedupe by client ``request_id`` in a bounded TTL
cache (``serve.replay_dedupe_hits``), so a router-side timeout retry
returns the cached result instead of recomputing. The driver's
dead-host set (scope ``serve`` key ``dead_hosts``,
runner/rendezvous.py) evicts announcements immediately — routing never
waits out the freshness window on a host the control plane already
declared dead. ``HOROVOD_SERVE_HEDGE_MS`` arms tail-latency hedging:
a backup request fires after the delay, first writer wins
(``serve.hedges``).
"""

from __future__ import annotations

import json
import socket
import threading
import time
import uuid
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

from ..common import tracing as _tracing
from ..common.logging import TRACE as _TRACE, get_logger
from ..common.metrics import registry as _metrics
from ..common.telemetry import (
    PROM_CONTENT_TYPE,
    hub as _telemetry_hub,
    render_prometheus,
)
from .batcher import ContinuousBatcher, Rejected

_log = get_logger("serve.frontend")

SERVE_SCOPE = "serve"
DEFAULT_ANNOUNCE_INTERVAL_S = 1.0
# announcements older than this are a dead/partitioned worker as far
# as routing is concerned
DEFAULT_ANNOUNCE_TTL_S = 10.0
# completed-result dedupe cache bound (entries): TTL prunes first, this
# caps worst-case memory under a flood of unique request_ids
DEDUPE_MAX_ENTRIES = 1024


def put_announcement(client, rank: int, payload: dict) -> None:
    """Worker side of the capacity ledger (KVStore or RendezvousClient
    surface — the same duality as heartbeats)."""
    client.put(SERVE_SCOPE, str(int(rank)), json.dumps(payload).encode())


def read_announcements(store_or_client) -> Dict[int, dict]:
    out: Dict[int, dict] = {}
    for key in store_or_client.keys(SERVE_SCOPE):
        raw = store_or_client.get(SERVE_SCOPE, key)
        if raw is None:
            continue
        try:
            rank = int(key)
            obj = json.loads(raw.decode())
        except (ValueError, UnicodeDecodeError):
            continue
        if isinstance(obj, dict) and "port" in obj:
            out[rank] = obj
    return out


class ServeFrontend:
    def __init__(
        self,
        batcher: ContinuousBatcher,
        port: int = 0,
        addr: str = "0.0.0.0",
        advertise_addr: str = "127.0.0.1",
        rank: Optional[int] = None,
        announce_client=None,
        announce_interval_s: float = DEFAULT_ANNOUNCE_INTERVAL_S,
        transfer_server=None,
    ) -> None:
        self.batcher = batcher
        # KVTransferServer on decode-role workers: its port travels in
        # the capacity blob, its unexpired reservations debit the
        # announced page headroom
        self.transfer_server = transfer_server
        self.advertise_addr = advertise_addr
        self.rank = self._resolve_rank(rank)
        self._announce_client = announce_client
        self._announce_interval = float(announce_interval_s)
        self._announce_stop = threading.Event()
        self._announce_thread: Optional[threading.Thread] = None
        self._draining = False
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        # completed-result dedupe cache: client request_id → (result,
        # expiry). A Router replay or client retry of work this worker
        # already finished returns the cached result — the idempotency
        # half of crash-safe serving (a retry after a router-side
        # timeout must not recompute, and MUST answer even mid-drain).
        self._dedupe: "OrderedDict[str, tuple]" = OrderedDict()
        self._dedupe_lock = threading.Lock()
        # client-visible status mix (/generate replies only): the
        # failure ladder counts replays/fallbacks, this counts what the
        # CLIENT saw (docs/robustness.md runbook row)
        self._status_lock = threading.Lock()
        self._status_counts = {2: 0, 4: 0, 5: 0}
        # live-migration coordinator, built lazily on the first
        # deadline-bounded drain (unified workers have no transfer
        # coordinator wired otherwise)
        self._migrator = None
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                _log.log(_TRACE, "http " + fmt, *args)

            def _reply(
                self, code, body: bytes, ctype: str, headers=None,
            ) -> None:
                self._last_code = code
                if getattr(self, "_count_status", False):
                    outer._note_status(code)
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _json(self, code, obj, headers=None) -> None:
                self._reply(
                    code, json.dumps(obj).encode(), "application/json",
                    headers=headers,
                )

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/healthz":
                    return self._json(200, outer.capacity())
                if path == "/stats":
                    stats = dict(outer.batcher.stats())
                    stats.update(outer.batcher.engine.stats())
                    stats["slo"] = outer.batcher.recorder.summaries()
                    return self._json(200, stats)
                if path == "/metrics":
                    hub = _telemetry_hub()
                    body = "\n".join(
                        outer.batcher.recorder
                        .render_prometheus_summaries()
                    ) + "\n" + render_prometheus(
                        _metrics.snapshot(), hub.percentiles()
                    )
                    return self._reply(
                        200, body.encode(), PROM_CONTENT_TYPE
                    )
                if path == "/traces":
                    # span ring + identity + clock stamps (same payload
                    # as the MetricsServer route): serve workers run
                    # their own HTTP plane, and trace_assemble must be
                    # able to scrape them live — the scrape itself is
                    # an NTP edge for the skew-corrected assembly
                    recv_ts = time.time()
                    rec = _tracing.recorder()
                    return self._json(200, {
                        "spans": rec.spans(),
                        "capacity": rec.capacity,
                        "host": rec.host,
                        "pid": rec.pid,
                        "role": rec.role,
                        "recv_ts": recv_ts,
                        "send_ts": time.time(),
                    })
                return self._reply(
                    404, b"not found\n", "text/plain; charset=utf-8"
                )

            def do_POST(self):
                # read the body FIRST: HTTP/1.1 keep-alive means an
                # early reply that leaves body bytes on the socket
                # desynchronizes the connection's next request
                recv_ts = time.time()
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length) if length else b""
                path = self.path.split("?", 1)[0]
                if path != "/generate":
                    return self._reply(
                        404, b"not found\n", "text/plain; charset=utf-8"
                    )
                # client-visible status mix: counted for the request
                # surface only, never the scrape GETs
                self._count_status = True
                # trace plane: adopt the incoming traceparent (or mint
                # a root when tracing is on and the client brought
                # none); every reply echoes X-Trace-Id plus the
                # recv/send clock stamps the assembler's skew
                # estimation feeds on. tctx None (the default) costs
                # nothing downstream.
                tctx = _tracing.adopt(
                    self.headers.get(_tracing.TRACEPARENT_HEADER)
                )
                span = _tracing.start_span("http.generate", tctx)
                hdrs = None
                if tctx is not None:
                    hdrs = _tracing.server_stamps(recv_ts)
                    hdrs[_tracing.TRACE_ID_HEADER] = tctx.trace_id
                try:
                    return self._generate(body, span, hdrs)
                finally:
                    self._count_status = False
                    if span is not None:
                        span.end(code=getattr(self, "_last_code", 0))

            def _generate(self, body, span, hdrs):
                trace_ctx = span.ctx if span is not None else None
                try:
                    payload = json.loads(body or b"{}")
                    if not isinstance(payload, dict):
                        raise ValueError(
                            f"body must be a JSON object, got "
                            f"{type(payload).__name__}"
                        )
                    tokens = payload["tokens"]
                except (json.JSONDecodeError, KeyError, ValueError) as e:
                    return self._json(
                        400, {"error": f"bad request: {e}"}, headers=hdrs
                    )
                request_id = str(payload.get("request_id") or "")
                if span is not None and request_id:
                    span.tag(request_id=request_id)
                if request_id:
                    # the dedupe check runs BEFORE the draining gate: a
                    # retry for work this worker already completed must
                    # get its cached answer even mid-drain — that's the
                    # whole point of keying results by request_id
                    hit = outer._dedupe_get(request_id)
                    if hit is not None:
                        _metrics.counter("serve.replay_dedupe_hits")
                        if span is not None:
                            span.tag(outcome="dedupe_hit")
                        return self._json(200, hit, headers=hdrs)
                if outer.draining:
                    return self._json(
                        503, {"error": "draining", "retry": True},
                        headers=hdrs,
                    )
                with outer._inflight_lock:
                    outer._inflight += 1
                try:
                    try:
                        req = outer.batcher.submit(
                            tokens,
                            max_new_tokens=payload.get("max_tokens"),
                            deadline_ms=payload.get("deadline_ms"),
                            temperature=float(
                                payload.get("temperature", 0.0)
                            ),
                            top_k=int(payload.get("top_k", 0)),
                            seed=payload.get("seed"),
                            trace=trace_ctx,
                        )
                    except Rejected as e:
                        # draining (planned or crash) is the WORKER's
                        # state -> 503 so the Router fails over; 429 is
                        # reserved for requests that can never fit
                        code = 503 if outer.draining else 429
                        return self._json(
                            code, {"error": str(e)}, headers=hdrs
                        )
                    except (TypeError, ValueError) as e:
                        # well-formed JSON, malformed fields (string
                        # tokens, non-numeric budgets): the client's
                        # fault, so the client gets told — not a torn
                        # socket the router misreads as a dead worker
                        return self._json(
                            400, {"error": f"bad request: {e}"},
                            headers=hdrs,
                        )
                    req.wait()
                    # "error" = the scheduler crashed under this
                    # request (batcher._abort_all): a worker fault,
                    # 500 so the router fails over instead of the
                    # client treating it as a completion
                    code = 500 if req.status == "error" else 200
                    result = req.result()
                    if span is not None:
                        span.tag(outcome=req.status)
                    if request_id and code == 200:
                        outer._dedupe_put(request_id, result)
                    return self._json(code, result, headers=hdrs)
                finally:
                    with outer._inflight_lock:
                        outer._inflight -= 1

        class _Server(ThreadingHTTPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._httpd = _Server((addr, port), _Handler)
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def _resolve_rank(rank: Optional[int]) -> int:
        if rank is not None:
            return int(rank)
        from ..common import basics

        if basics.is_initialized():
            return basics.rank()
        cfg = basics.live_config()
        return cfg.rank if cfg.rank is not None else 0

    # ------------------------------------------------------------ lifecycle

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def draining(self) -> bool:
        """Planned drain OR the batcher's crash-drain: either way this
        worker takes no new requests, and every surface (503s,
        /healthz, the KV announcement) must say so consistently."""
        return self._draining or self.batcher.draining

    def capacity(self) -> dict:
        mgr = self.batcher.engine.manager.stats()
        draining = self.draining
        cap = {
            "ok": not draining,
            "rank": self.rank,
            "addr": self.advertise_addr,
            "port": self.port,
            "role": getattr(self.batcher, "role", "unified"),
            "free_slots": mgr["slots_free"],
            "slots_total": mgr["slots_total"],
            "queue_depth": self.batcher.queue_depth(),
            "draining": draining,
            # the driver's dead-host set names HOSTS (its blacklist
            # unit); announcing ours lets the Router match either way
            "host": socket.gethostname(),
            "ts": time.time(),
        }
        if self.transfer_server is not None:
            cap["transfer_port"] = self.transfer_server.port
        if "pages_total" in mgr:
            # paged memory plane: page headroom is the truthful
            # capacity signal (admission is gated on it, not on
            # slots). free_pages is watermark-adjusted — what
            # admission may actually spend — and a SATURATED pool
            # flips the slot capacity to 0 too, so even a
            # slots-only/legacy Router steers away from a worker
            # that would only queue the request.
            manager = self.batcher.engine.manager
            free_pages = manager.admission_headroom()
            if self.transfer_server is not None:
                # pages promised to in-flight transfers are spoken for:
                # two senders must not both be told the same headroom
                free_pages = max(
                    free_pages - self.transfer_server.reserved_pages(), 0
                )
            cap["free_pages"] = free_pages
            cap["pages_total"] = mgr["pages_total"]
            cap["prefix_hit_rate"] = round(mgr["prefix_hit_rate"], 4)
            if free_pages <= 0:
                cap["free_slots"] = 0
            if cap["free_slots"] <= 0:
                # the symmetric clamp: admission needs a slot AND
                # pages, so a slot-saturated worker must not look
                # page-rich to a Router that prefers page headroom
                cap["free_pages"] = 0
        return cap

    def start(self) -> int:
        if self._thread is not None:
            return self.port
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="hvd-serve-frontend",
            daemon=True,
        )
        self._thread.start()
        client = self._resolve_announce_client()
        if client is not None:
            self._announce_client = client
            self._announce_stop.clear()
            self._announce_thread = threading.Thread(
                target=self._announce_loop,
                name="hvd-serve-announce",
                daemon=True,
            )
            self._announce_thread.start()
        _log.info(
            "serve frontend on port %d (rank %d)", self.port, self.rank
        )
        return self.port

    def _resolve_announce_client(self):
        if self._announce_client is not None:
            return self._announce_client
        from ..common import basics

        cfg = basics.live_config()
        if not cfg.rendezvous_addr or not cfg.rendezvous_port:
            return None
        from ..runner.rendezvous import _client_from_cfg

        return _client_from_cfg(cfg)

    def _announce_loop(self) -> None:
        while not self._announce_stop.is_set():
            self.announce()
            self._announce_stop.wait(self._announce_interval)

    def announce(self) -> None:
        """One capacity PUT into the rendezvous KV (scope ``serve``)."""
        if self._announce_client is None:
            return
        try:
            put_announcement(
                self._announce_client, self.rank, self.capacity()
            )
        except (OSError, RuntimeError) as e:
            _log.debug("serve announce failed: %s", e)

    def drain(
        self, timeout: float = 30.0,
        migrate_after: Optional[float] = None,
    ) -> bool:
        """SIGTERM half of the lifecycle: refuse new work, finish the
        accepted work, let the in-flight responses flush. Announces the
        drained state so the router stops sending traffic.

        ``migrate_after`` (default: ``HOROVOD_SERVE_DRAIN_DEADLINE_S``;
        0 = off) bounds how long in-flight sequences may keep decoding
        locally: past it, they are live-migrated to a reserved peer
        over the kv_transfer wire and finish there — the preemption
        grace window is honored without dropping a request."""
        self._draining = True
        self.announce()
        if migrate_after is None:
            from ..common import basics

            deadline_s = basics.live_config().serve_drain_deadline_s
            migrate_after = deadline_s if deadline_s > 0 else None
        if migrate_after is not None and self.batcher.engine.paged:
            ok = self.batcher.drain(
                timeout=timeout,
                migrate_after=float(migrate_after),
                on_deadline=self._migrate_inflight,
            )
        else:
            ok = self.batcher.drain(timeout=timeout)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with self._inflight_lock:
                if self._inflight == 0:
                    break
            time.sleep(0.01)
        self.announce()
        return ok

    def _resolve_migrator(self):
        """The TransferCoordinator the deadline drain streams through:
        a prefill worker reuses the batcher's wired coordinator; other
        roles build one lazily against the same announcement channel."""
        if self.batcher.transfer is not None:
            return self.batcher.transfer
        if self._migrator is None:
            from .kv_transfer import TransferCoordinator

            self._migrator = TransferCoordinator(
                self.batcher.engine,
                client_factory=self._resolve_announce_client,
            )
        return self._migrator

    def _migrate_inflight(self, records) -> None:
        """batcher.drain's on_deadline hook: stream every exported
        in-flight record to a reserved peer; a record that can't go
        anywhere falls back to the local queue (the drain keeps
        stepping it inline). Never raises — a migration failure must
        degrade to the classic run-to-completion drain, not kill the
        drain thread."""
        try:
            coord = self._resolve_migrator()
        except Exception as e:  # noqa: BLE001 — degrade, don't die
            _log.warning(
                "no migration coordinator (%s); draining %d sequence(s) "
                "locally", e, len(records),
            )
            coord = None
        for rec in records:
            if coord is None:
                self.batcher.requeue_fallback(
                    rec["req"], rec["kept"], rec["length"]
                )
                continue
            try:
                coord.migrate(self.batcher, rec)
            except Exception as e:  # noqa: BLE001 — per-record fallback
                _log.warning(
                    "migration of request %d failed at export (%s); "
                    "falling back to local decode", rec["req"].id, e,
                )
                self.batcher.requeue_fallback(
                    rec["req"], rec["kept"], rec["length"]
                )

    def _note_status(self, code: int) -> None:
        """Per-reply status accounting on the request surface:
        ``serve.http_2xx/4xx/5xx`` counters plus the derived
        ``serve.http_error_rate`` gauge (non-2xx fraction of every
        /generate reply this worker ever sent)."""
        klass = int(code) // 100
        if klass not in (2, 4, 5):
            klass = 5 if klass > 5 else 4
        with self._status_lock:
            self._status_counts[klass] += 1
            counts = dict(self._status_counts)
        _metrics.counter(f"serve.http_{klass}xx")
        total = sum(counts.values())
        if total:
            _metrics.gauge(
                "serve.http_error_rate",
                (counts[4] + counts[5]) / total,
            )

    # ----------------------------------------------------------- dedupe cache

    def _dedupe_get(self, request_id: str) -> Optional[dict]:
        with self._dedupe_lock:
            hit = self._dedupe.get(request_id)
            if hit is None:
                return None
            result, expiry = hit
            if time.monotonic() >= expiry:
                del self._dedupe[request_id]
                return None
            return result

    def _dedupe_put(self, request_id: str, result: dict) -> None:
        from ..common import basics

        ttl = float(basics.live_config().serve_dedupe_ttl_s)
        if ttl <= 0:
            return
        now = time.monotonic()
        with self._dedupe_lock:
            for k in [
                k for k, (_, exp) in self._dedupe.items() if exp <= now
            ]:
                del self._dedupe[k]
            self._dedupe[request_id] = (result, now + ttl)
            self._dedupe.move_to_end(request_id)
            while len(self._dedupe) > DEDUPE_MAX_ENTRIES:
                self._dedupe.popitem(last=False)

    def stop(self) -> None:
        self._announce_stop.set()
        if self._announce_thread is not None:
            self._announce_thread.join(timeout=5)
            self._announce_thread = None
        if self._thread is None:
            self._httpd.server_close()
            return
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)
        self._thread = None


class Router:
    """Thin fleet router over the rendezvous KV: capacity announcements
    in, straggler ledger in, pick-and-forward out. Stateless apart from
    a local free-slot debit so a burst routed between two announcement
    refreshes spreads instead of piling onto one worker."""

    def __init__(
        self,
        store_or_client,
        straggler_factor: Optional[float] = None,
        announce_ttl_s: float = DEFAULT_ANNOUNCE_TTL_S,
    ) -> None:
        self._store = store_or_client
        self._ttl = float(announce_ttl_s)
        from ..common.stall_inspector import StallInspector

        self._inspector = StallInspector(
            straggler_factor=(
                3.0 if straggler_factor is None else straggler_factor
            )
        )
        self._debits: Dict[int, int] = {}
        # rank -> (last announced ts value, local monotonic stamp of
        # when it last CHANGED): freshness is judged in the router's
        # clock domain, so cross-host wall-clock skew can't silently
        # drop a live worker (or keep a dead one) from routing
        self._seen_ts: Dict[int, tuple] = {}
        # rank -> (announced ts at failure, monotonic expiry): a worker
        # that failed a live call is tombstoned for one freshness
        # period — its pre-crash announcement must not re-attract the
        # NEXT request; a ts ADVANCE (the worker actually announcing
        # again) clears it early
        self._tombstones: Dict[int, tuple] = {}
        self._lock = threading.Lock()

    def snapshot(self) -> Dict[int, dict]:
        """Live worker view: non-draining announcements whose ts keeps
        ADVANCING, freshness judged on the router's own monotonic
        clock. First sight of a rank has no change history, so the
        announced wall ts is the tiebreak there (a wall-stale leftover
        from a dead worker stays out); after that only advancement
        counts, so a live worker with a skewed clock re-qualifies on
        its next announce instead of being silently unroutable."""
        now = time.monotonic()
        out = {}
        dead_hosts, dead_ranks = self._dead_set()
        with self._lock:
            for rank, ann in read_announcements(self._store).items():
                if ann.get("draining"):
                    continue
                if (
                    rank in dead_ranks
                    or str(ann.get("host") or "") in dead_hosts
                    or str(ann.get("addr") or "") in dead_hosts
                ):
                    # the driver already declared this host dead: evict
                    # NOW instead of waiting out the freshness window
                    continue
                ts = float(ann.get("ts", 0))
                tomb = self._tombstones.get(rank)
                if tomb is not None:
                    if ts == tomb[0] and now < tomb[1]:
                        # the same blob the worker announced before it
                        # failed a live call: a pre-crash leftover
                        continue
                    # ts advanced (the worker is actually alive) or
                    # the tombstone aged out: forgive
                    del self._tombstones[rank]
                prev = self._seen_ts.get(rank)
                if prev is None:
                    # wall tiebreak, once: mark wall-stale first sights
                    # as already-expired; they revive on any advance
                    wall_fresh = abs(time.time() - ts) <= self._ttl
                    stamp = now if wall_fresh else now - self._ttl - 1
                    self._seen_ts[rank] = (ts, stamp)
                    if wall_fresh:
                        out[rank] = ann
                elif prev[0] != ts:
                    self._seen_ts[rank] = (ts, now)
                    out[rank] = ann
                elif now - prev[1] <= self._ttl:
                    out[rank] = ann
        return out

    def _dead_set(self):
        """The driver's published dead/quarantined set (scope ``serve``
        key ``dead_hosts``): hostnames + the serve ranks mapped onto
        them at publication. Empty on any read failure — the dead set
        accelerates eviction, it never blocks routing."""
        from ..runner.rendezvous import read_dead_hosts

        try:
            dead = read_dead_hosts(self._store)
        except (OSError, RuntimeError, ValueError):
            return set(), set()
        return (
            {str(h) for h in dead.get("hosts", ())},
            {int(r) for r in dead.get("ranks", ())},
        )

    def tombstone(self, rank: int, ann: Optional[dict] = None) -> None:
        """Mark a worker that failed a LIVE call: its current
        announcement stays unroutable for one freshness period (or
        until the worker announces a newer ts — proof of life)."""
        with self._lock:
            self._tombstones[int(rank)] = (
                float((ann or {}).get("ts", 0.0)),
                time.monotonic() + self._ttl,
            )

    def straggler_ranks(self) -> List[int]:
        """The PR 4 ledger, read fleet-side: feed every heartbeat's
        piggybacked step stats into a StallInspector and flag the slow
        ranks — the routing table's deny list."""
        from ..runner.rendezvous import read_heartbeat_stats

        try:
            stats = read_heartbeat_stats(self._store)
        except (OSError, RuntimeError):
            return []
        for rank, payload in stats.items():
            self._inspector.record_heartbeat(
                rank,
                ts=payload.get("ts"),
                step=payload.get("step"),
                step_ms_p50=payload.get("step_ms_p50"),
                last_step_ts=payload.get("last_step_ts"),
            )
        return self._inspector.straggler_ranks()

    def pick(self, exclude=()) -> Optional[dict]:
        """The least-loaded live worker whose rank is not flagged by
        the straggler ledger; flagged workers are only used when they
        are ALL that is left (degraded beats down). ``exclude`` drops
        ranks a caller already failed against in this routing round."""
        from .kv_transfer import worker_role

        workers = self.snapshot()
        for rank in exclude:
            workers.pop(rank, None)
        # role split: decode workers take KV transfers, never prompts —
        # they are not /generate candidates. When prefill workers exist
        # they take every fresh admission (that IS the disaggregation);
        # unified workers carry the traffic otherwise. worker_role()
        # maps blobs with NO role field (old workers mid-rollout) to
        # "unified", so a mixed-version fleet keeps routing.
        workers = {
            r: w for r, w in workers.items()
            if worker_role(w) != "decode"
        }
        prefill = {
            r: w for r, w in workers.items()
            if worker_role(w) == "prefill"
        }
        workers = prefill or workers
        if not workers:
            return None
        flagged = set(self.straggler_ranks())
        healthy = {r: w for r, w in workers.items() if r not in flagged}
        pool = healthy or workers
        if not healthy:
            _log.warning(
                "all serve workers flagged as stragglers (%s); routing "
                "to flagged rank anyway", sorted(flagged),
            )
        with self._lock:
            def load(item):
                rank, w = item
                # page headroom gates admission on the paged plane, but
                # every admission ALSO needs a slot — min() folds both
                # into request-capacity units, so a page-rich worker
                # with one free slot can't outrank an idle slab worker,
                # and the 1-per-route debit below subtracts in the same
                # unit. Old announcements carrying only free_slots keep
                # routing exactly as before — mixed fleets mid-rollout
                # stay routable.
                pages = w.get("free_pages")
                slots_free = w.get("free_slots", 0)
                if pages is None:
                    free = slots_free
                else:
                    free = min(int(slots_free), int(pages))
                free -= self._debits.get(rank, 0)
                return (-free, w.get("queue_depth", 0), rank)

            rank, ann = min(pool.items(), key=load)
            self._debits[rank] = self._debits.get(rank, 0) + 1
            return dict(ann, rank=rank)

    def credit(self, rank: int) -> None:
        """Return a debit after a routed request completes."""
        with self._lock:
            if self._debits.get(rank, 0) > 0:
                self._debits[rank] -= 1

    def _post_generate(self, ann: dict, body: bytes,
                       timeout: float, span=None) -> dict:
        """One /generate POST against one worker — the routing unit
        every path (sequential, replay, hedge arm) shares. With a leg
        ``span``, the traceparent header carries its context to the
        worker and the reply's clock-stamp echo is tagged onto it (the
        NTP edge the skew-corrected assembly estimates offsets from)."""
        import urllib.request

        url = (
            f"http://{ann.get('addr', '127.0.0.1')}:{ann['port']}"
            f"/generate"
        )
        headers = {"Content-Type": "application/json"}
        if span is not None:
            headers[_tracing.TRACEPARENT_HEADER] = (
                span.ctx.to_traceparent()
            )
        req = urllib.request.Request(
            url, data=body, headers=headers, method="POST",
        )
        t_send = time.time()
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            out = json.loads(resp.read().decode())
            _tracing.tag_hop(span, t_send, time.time(), resp.headers)
        return out

    def _note_failure(self, ann: dict, err: Exception, span=None) -> None:
        """Classify a failed live call. A 503 is an ORDERLY refusal
        (draining/rejected before admission) — plain failover, the
        worker's own announcement will say so. Everything else (5xx,
        transport fault, torn response) means the worker went dark with
        the request possibly in flight: the retry on the next candidate
        is a REPLAY (``serve.replays``) and the dark worker's stale
        announcement is tombstoned so it can't re-attract traffic.
        The leg ``span``, when traced, closes tagged with the same
        classification."""
        import urllib.error

        _metrics.counter("serve.route_failover")
        if isinstance(err, urllib.error.HTTPError) and err.code == 503:
            if span is not None:
                span.end(outcome="failover", code=503)
            return
        _metrics.counter("serve.replays")
        if span is not None:
            span.end(
                outcome="replayed",
                error=f"{type(err).__name__}: {err}",
            )
        self.tombstone(ann["rank"], ann)

    def route(
        self,
        tokens,
        max_tokens: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        timeout: float = 60.0,
        attempts: int = 3,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: Optional[int] = None,
        request_id: Optional[str] = None,
        hedge_ms: Optional[float] = None,
        trace=None,
    ) -> dict:
        """POST /generate on the picked worker; a dead or draining pick
        fails over to the next candidate — the full submission below IS
        the durability journal, so a worker that dies mid-call gets the
        request transparently REPLAYED on a live one, idempotent by
        ``request_id`` (generated here when the client brings none; the
        workers' dedupe cache keys on it). Sampling knobs ride the
        payload verbatim (temperature 0 = greedy; a caller-pinned seed
        keeps a replayed request reproducible on whichever worker
        serves it). ``hedge_ms`` (default ``HOROVOD_SERVE_HEDGE_MS``,
        0 = off) fires a backup request on a second worker after the
        delay — first writer wins, the loser is discarded."""
        import urllib.error

        payload: dict = {"tokens": list(map(int, tokens))}
        if max_tokens is not None:
            payload["max_tokens"] = int(max_tokens)
        if deadline_ms is not None:
            payload["deadline_ms"] = float(deadline_ms)
        if temperature:
            payload["temperature"] = float(temperature)
        if top_k:
            payload["top_k"] = int(top_k)
        if seed is not None:
            payload["seed"] = int(seed)
        payload["request_id"] = str(request_id or uuid.uuid4().hex)
        body = json.dumps(payload).encode()
        last_err: Optional[Exception] = None
        failed: set = set()
        # trace plane: the routing side mints the request's root
        # context (or adopts the caller's); every leg below — first
        # try, replay, hedge arm — is a SIBLING span under it tagged
        # with its outcome, and the traceparent header carries the
        # leg's context to the worker it hits.
        tctx = trace if trace is not None else _tracing.mint()
        root = _tracing.root_span(
            "route", tctx, request_id=payload["request_id"]
        )
        try:
            if hedge_ms is None:
                from ..common import basics

                hedge_ms = basics.live_config().serve_hedge_ms
            if hedge_ms and float(hedge_ms) > 0:
                out, failed, last_err = self._route_hedged(
                    body, timeout, float(hedge_ms) / 1e3, tctx=tctx,
                )
                if out is not None:
                    if root is not None:
                        root.tag(outcome="ok")
                        out.setdefault("trace_id", tctx.trace_id)
                    return out
                # both arms dark: fall through to the sequential replay
                # loop with the failed ranks already excluded
            for _ in range(max(int(attempts), 1)):
                ann = self.pick(exclude=failed)
                if ann is None:
                    if failed:
                        raise RuntimeError(
                            f"routing failed: every live worker errored "
                            f"({sorted(failed)}): {last_err}"
                        )
                    raise RuntimeError("no live serve workers announced")
                leg = _tracing.start_span(
                    "route.attempt", tctx, rank=int(ann["rank"]),
                    mode="replay" if failed else "first",
                )
                try:
                    out = self._post_generate(
                        ann, body, timeout, span=leg
                    )
                    if leg is not None:
                        leg.end(outcome="ok")
                        out.setdefault("trace_id", tctx.trace_id)
                    if root is not None:
                        root.tag(outcome="ok")
                    return out
                except urllib.error.HTTPError as e:
                    if e.code == 503 or e.code >= 500:
                        # draining / server fault: the WORKER's problem,
                        # fail over to the next candidate
                        last_err = e
                        failed.add(ann["rank"])
                        self._note_failure(ann, e, span=leg)
                        continue
                    # 4xx: the REQUEST's problem — every worker would
                    # say the same thing; surface the actionable error
                    # instead of burning the fleet and masking it as
                    # 'all dead'
                    if leg is not None:
                        leg.end(outcome="rejected", code=e.code)
                    try:
                        detail = json.loads(
                            e.read().decode()
                        ).get("error", "")
                    except (ValueError, OSError):
                        detail = ""
                    raise RuntimeError(
                        f"request rejected by rank {ann['rank']} "
                        f"(HTTP {e.code}): {detail or e.reason}"
                    ) from e
                except (OSError, ValueError) as e:
                    last_err = e
                    failed.add(ann["rank"])
                    self._note_failure(ann, e, span=leg)
                    continue
                finally:
                    self.credit(ann["rank"])
            raise RuntimeError(
                f"routing failed after {attempts} attempts: {last_err}"
            )
        finally:
            if root is not None:
                if "outcome" not in root.tags:
                    root.tag(outcome="error")
                root.end()

    def _route_hedged(
        self, body: bytes, timeout: float, hedge_s: float, tctx=None,
    ):
        """Primary fires immediately; if no result lands within
        ``hedge_s`` a backup fires on a second worker
        (``serve.hedges``). First writer wins — the losing arm's
        response is discarded when it eventually lands. Returns
        ``(result_or_None, failed_ranks, last_err)``; the caller's
        sequential loop finishes the job when every arm went dark. Each
        arm gets its own ``route.attempt`` sibling span under ``tctx``
        tagged ``hedge=primary|backup`` — won/discarded/error outcomes
        make the race legible in the assembled trace."""
        primary = self.pick()
        if primary is None:
            return None, set(), None
        cv = threading.Condition()
        box: dict = {"errors": []}

        def arm(ann, hedge_tag):
            leg = _tracing.start_span(
                "route.attempt", tctx,
                rank=int(ann["rank"]), hedge=hedge_tag,
            )
            try:
                out = self._post_generate(ann, body, timeout, span=leg)
            except Exception as e:  # noqa: BLE001 — arm failure is data
                with cv:
                    box["errors"].append((ann, e, leg))
                    cv.notify_all()
            else:
                with cv:
                    won = "result" not in box
                    box.setdefault("result", out)
                    cv.notify_all()
                if leg is not None:
                    leg.end(outcome="ok" if won else "discarded")
            finally:
                self.credit(ann["rank"])

        threading.Thread(
            target=arm, args=(primary, "primary"),
            name="hvd-route-primary", daemon=True,
        ).start()
        arms = 1
        deadline = time.monotonic() + timeout
        with cv:
            cv.wait(timeout=hedge_s)
            if "result" not in box and not box["errors"]:
                backup = self.pick(exclude={primary["rank"]})
                if backup is not None:
                    _metrics.counter("serve.hedges")
                    arms = 2
                    threading.Thread(
                        target=arm, args=(backup, "backup"),
                        name="hvd-route-hedge", daemon=True,
                    ).start()
            while "result" not in box and len(box["errors"]) < arms:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not cv.wait(timeout=remaining):
                    break
            errors = list(box["errors"])
            result = box.get("result")
        failed: set = set()
        last_err: Optional[Exception] = None
        for ann, err, leg in errors:
            failed.add(ann["rank"])
            last_err = err
            self._note_failure(ann, err, span=leg)
        return result, failed, last_err


class ServeHandle:
    """What ``hvd.serve`` returns: the running plane + its lifecycle."""

    def __init__(
        self, engine, batcher, frontend, shutdown_ctx=None,
        transfer_server=None,
    ):
        self.engine = engine
        self.batcher = batcher
        self.frontend = frontend
        self.transfer_server = transfer_server
        self._shutdown_ctx = shutdown_ctx
        self._stopped = threading.Event()

    @property
    def port(self) -> int:
        return self.frontend.port

    def drain(self, timeout: float = 30.0) -> bool:
        return self.frontend.drain(timeout=timeout)

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until stop() — the serve-worker main thread parks
        here (SIGTERM interrupts via the drain hook + process exit)."""
        return self._stopped.wait(timeout)

    def stop(self) -> None:
        from .. import preemption

        preemption.unregister_drain(self._drain_hook)
        self.frontend.stop()
        self.batcher.stop()
        if self.transfer_server is not None:
            self.transfer_server.stop()
        if self._shutdown_ctx is not None:
            self._shutdown_ctx.__exit__(None, None, None)
            self._shutdown_ctx = None
        self._stopped.set()

    # bound per-handle so unregister removes exactly this plane's hook
    def _drain_hook(self) -> None:
        self.frontend.drain()


def serve(
    model,
    params,
    port: Optional[int] = None,
    *,
    slots: Optional[int] = None,
    max_len: Optional[int] = None,
    max_new_tokens: Optional[int] = None,
    deadline_ms: Optional[float] = None,
    max_admit_per_step: Optional[int] = None,
    eos_id: Optional[int] = None,
    policy: str = "continuous",
    addr: str = "0.0.0.0",
    advertise_addr: str = "127.0.0.1",
    rank: Optional[int] = None,
    announce_client=None,
    mesh=None,
    handle_sigterm: bool = True,
    role: Optional[str] = None,
    kv_wire: Optional[str] = None,
    transfer_port: Optional[int] = None,
    **engine_kwargs,
) -> ServeHandle:
    """Start the inference plane on this worker: engine + continuous
    batcher + HTTP frontend, drain-wired into the preemption path.

    The Horovod-paper API shape (arXiv 1802.05799: bolt distributed
    execution onto an existing model with minimal surface): ``model`` is
    the same flax module you trained, ``params`` the tree you
    checkpointed — ``hvd.serve(model, params, port=8500)`` and the
    worker serves. Env defaults: ``HOROVOD_SERVE_PORT``,
    ``_SERVE_KV_SLOTS``, ``_SERVE_MAX_BATCH``, ``_SERVE_MAX_TOKENS``,
    ``_SERVE_DEADLINE_MS`` (docs/env_vars.md).

    ``handle_sigterm=True`` (default) installs a
    ``preemption.GracefulShutdown(None)`` so a bare serve worker drains
    on SIGTERM and exits 143; pass False when composing with your own
    ``GracefulShutdown`` — the drain hook this function registers via
    ``preemption.register_drain`` makes YOUR shutdown drain the serving
    plane first, before telemetry/checkpoint.
    """
    from ..common import basics, compile_cache
    from .. import preemption
    from .engine import InferenceEngine

    compile_cache.ensure()  # serve() does not need hvd.init()
    cfg = basics.live_config()
    # Label this worker's spans with its serving role so the trace
    # assembler gets one row per (host, role) without guessing.
    _tracing.set_role(role or cfg.serve_role)
    if port is None:
        port = cfg.serve_port
    if slots is None:
        slots = cfg.serve_kv_slots
    if max_new_tokens is None:
        max_new_tokens = cfg.serve_max_tokens
    if deadline_ms is None:
        deadline_ms = cfg.serve_deadline_ms
    if max_admit_per_step is None:
        max_admit_per_step = cfg.serve_max_batch
    if role is None:
        role = cfg.serve_role
    if kv_wire is None:
        kv_wire = cfg.serve_kv_wire
    else:
        # Validate here even though only prefill workers build the
        # TransferCoordinator — a typo'd wire on a decode/unified worker
        # must fail at serve() time, not when the fleet is re-roled.
        from .kv_transfer import WIRE_FORMATS

        if kv_wire not in WIRE_FORMATS:
            raise ValueError(
                f"kv wire must be one of {WIRE_FORMATS}, got {kv_wire!r}"
            )
    if transfer_port is None:
        transfer_port = cfg.serve_transfer_port
    if max_len is None:
        model_cfg = getattr(model, "cfg", None)
        max_len = getattr(model_cfg, "max_len", None)
        if max_len is None:
            raise TypeError(
                "max_len= is required when the model carries no "
                ".cfg.max_len to derive the KV capacity from"
            )
    engine = InferenceEngine(
        model, params, slots=slots, max_len=max_len, mesh=mesh,
        role=role, **engine_kwargs,
    )
    batcher = ContinuousBatcher(
        engine,
        max_admit_per_step=max_admit_per_step,
        default_max_new_tokens=max_new_tokens,
        default_deadline_ms=deadline_ms,
        eos_id=eos_id,
        policy=policy,
        role=role,
    )
    transfer_server = None
    if role == "decode" or (role == "unified" and engine.paged):
        # decode workers take prefill handoffs; paged unified workers
        # run the server too so a draining peer can live-migrate its
        # in-flight sequences here (the `migrate` frame) — a
        # single-role fleet is still evacuable
        from .kv_transfer import KVTransferServer

        transfer_server = KVTransferServer(
            batcher, port=transfer_port, addr=addr
        )
        transfer_server.start()
    frontend = ServeFrontend(
        batcher, port=port, addr=addr,
        advertise_addr=advertise_addr, rank=rank,
        announce_client=announce_client,
        transfer_server=transfer_server,
    )
    if role == "prefill":
        from .kv_transfer import TransferCoordinator

        # the coordinator reads the same serve-scope announcements the
        # frontend publishes into — resolved lazily so a fleet-less
        # prefill worker (no rendezvous) just decodes locally
        batcher.transfer = TransferCoordinator(
            engine, wire=kv_wire,
            client_factory=frontend._resolve_announce_client,
        )
    shutdown_ctx = None
    if handle_sigterm:
        shutdown_ctx = preemption.GracefulShutdown(None)
        shutdown_ctx.__enter__()
    handle = ServeHandle(
        engine, batcher, frontend, shutdown_ctx,
        transfer_server=transfer_server,
    )
    preemption.register_drain(handle._drain_hook)
    batcher.start()
    frontend.start()
    return handle
