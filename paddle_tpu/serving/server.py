"""Asyncio HTTP/SSE frontend over :class:`EngineCore`.

The missing network surface above the continuous-batching engine (ISSUE 3
tentpole): a dependency-free HTTP/1.1 server on stdlib ``asyncio``
streams — no framework — exposing

* ``POST /v1/completions`` — OpenAI-style JSON (``protocol.py``);
  ``stream=true`` answers Server-Sent Events, one ``data:`` event per
  token batch, terminated by ``data: [DONE]``;
* ``GET /healthz`` — liveness (200 while the process runs);
* ``GET /readyz`` — readiness (503 the instant a drain begins, or if the
  engine thread died);
* ``GET /metrics`` — Prometheus text exposition of the engine's
  registry, byte-identical to ``observability.start_metrics_server``
  for the same registry (shared ``metrics_page`` handler).

HTTP/1.1 connections are **persistent** (ISSUE 3 follow-up (a)): a
handler loops request → response on one socket until the client sends
``Connection: close``, goes idle past ``keepalive_timeout_s``, or the
response is an SSE stream (self-delimiting — the socket closes after
``data: [DONE]``).  HTTP/1.0 clients must opt in with
``Connection: keep-alive``.

Threading model — a FLEET of engine threads, N async handlers
(ISSUE 6; dp=1 is simply a fleet of one):

    asyncio loop (handlers)          engine thread i (owns replica i)
    ───────────────────────          ───────────────────────────────
    parse ──router──▶ submit q_i ──▶ add_request(trace_id=...)
    await handle.event   ◀─notify──  step(): prefill/decode/sample
    read req.output_tokens[cursor:]  retire finished
    deadline hit ──owner──▶ abort q_i▶ abort_request(rid, TIMEOUT)

``EngineCore`` is not thread-safe and its jitted steps block, so each
replica runs its own background thread (``serving.fleet.EngineReplica``
— the PR 3 bounded submit/abort queue bridge, per replica); handlers
never touch a scheduler.  The :class:`~paddle_tpu.serving.fleet
.FleetRouter` places each request by **prefix-affinity consistent
hashing** over its leading prompt blocks (least-loaded fallback), and
routes aborts through the request→replica owner map so a deadline or
disconnect reaches the replica that actually holds the blocks.
Handlers read each request's append-only ``output_tokens`` directly
(safe under the GIL).  The hand-off of a step's news costs the engine
thread ONE ``loop.call_soon_threadsafe`` a step however many streams are
open (none while the last one is still pending): the callback walks the
open handles ON the loop thread and wakes only those whose request has
something to read — a new token, a finish, a terminal handle
(``CompletionServer._notify`` / ``_wake_streams``;
``serving_stream_wakes_total`` and its two siblings on ``/metrics``).
What the LOOP thread does for the streams is three spans on the
profiler's clock (``observability.tracer.THREAD_SPANS``; no-ops while no
profiler runs): ``server.accept`` (a parsed request up to its hand-over
to the fleet), ``server.wake`` (that walk) and ``server.write`` (a chunk
built and handed to the socket), the last two sharing the request's
number.

The frontend owns three policies the engines deliberately do not:

* **admission control** — per replica: at most ``max_queue`` requests in
  flight on each; a POST gets ``429`` (+ ``Retry-After``,
  ``serving_admission_rejected_total``) only when EVERY eligible replica
  is at its cap.  All cross-thread queues are bounded
  (``queue.Queue(maxsize=...)`` — ``tools/check_bounded_metrics.py``
  lints this package).
* **per-request deadlines** — ``timeout`` in the body (clamped to
  ``max_timeout_s``, defaulting to ``default_timeout_s``); on expiry the
  handler propagates ``abort(TIMEOUT)`` through the router into the
  OWNING replica's scheduler, the request's blocks are freed, and the
  partial output is returned with ``finish_reason="timeout"``.
* **graceful drain** — ``shutdown()`` (or SIGTERM under the CLI) flips
  ``/readyz`` to 503 immediately and stops admitting fleet-wide;
  in-flight requests run to completion up to the drain deadline, then
  are aborted with TIMEOUT; every engine thread exits only once its pool
  is empty.

Per-replica health rides the router: a dead engine thread is excluded
from routing and the fleet serves on; ``/readyz`` (and POSTs) answer 503
only when the WHOLE fleet is down.  ``/readyz``'s body reports the fleet
shape — ``ok dp=N mp=M``.

Every request gets a trace id (``cmpl-<n>``) attached to the engine's
prefill/preempt/decode spans, so one request's lifecycle is
reconstructible from a single exported chrome trace.

Self-test (wired into the test suite)::

    JAX_PLATFORMS=cpu python -m paddle_tpu.serving.server --selftest
"""

from __future__ import annotations

import asyncio
import itertools
import json
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from ..observability.httpd import PROMETHEUS_CONTENT_TYPE, metrics_page
from ..observability.tracer import SpanTracer
from .engine import EngineCore
from .fleet import (
    FleetConfig,
    FleetDown,
    FleetRouter,
    FleetSaturated,
    SubmitHandle,
)
from .protocol import (
    SSE_DONE,
    CompletionRequest,
    ProtocolError,
    chunk_body,
    completion_body,
    error_body,
    parse_completion_request,
    sse_event,
    usage_body,
)
from .request import FinishReason

_MAX_HEADER_BYTES = 16384
# a waiting handler re-checks its request this often whatever happens: a
# safety net only — every token and finish a step produces reaches its
# handler through the step's wake-up (``_notify``), never through this
_POLL_S = 0.25
_ROUTES = ("/v1/completions", "/v1/requests", "/v1/debug/compiles",
           "/v1/debug/profile", "/v1/debug/audit", "/v1/debug/cache",
           "/v1/debug/alerts", "/v1/debug/history", "/v1/debug/wire",
           "/healthz", "/readyz", "/metrics")

# pre-registered metric names this module owns (tools/check_metrics_docs
# lints that each appears in README's metrics table)
METRIC_NAMES = (
    "serving_admission_rejected_total",
    "serving_http_requests_total",
    "serving_stream_wakes_total",
    "serving_stream_wakes_coalesced_total",
    "serving_stream_handles_woken_total",
)


@dataclass
class ServerConfig:
    host: str = "127.0.0.1"
    port: int = 0                 # 0 = ephemeral, read back from .port
    max_queue: int = 64           # per-replica engine-side in-flight cap
                                  # (must match FleetConfig.max_queue for
                                  # a pre-built fleet); the HTTP-side
                                  # in-flight set is capped at dp x this
    retry_after_s: int = 1        # 429 Retry-After hint
    default_timeout_s: Optional[float] = None   # None = no deadline
    max_timeout_s: float = 600.0
    drain_timeout_s: float = 5.0  # shutdown(): grace for in-flight work
    keepalive_timeout_s: float = 30.0  # idle wait for the NEXT request on
                                       # a persistent connection (also the
                                       # first-request header deadline)
    model_name: str = "paddle-tpu"
    tokenize: Optional[Callable[[str], List[int]]] = None


class _Handle(SubmitHandle):
    """One in-flight HTTP completion: the fleet's :class:`SubmitHandle`
    (rid / prompt / sampling / req / done / cancel_reason, routed and
    owned by one replica) plus the parsed protocol request and the
    asyncio waker created on the server's loop.  ``woken_at`` /
    ``woken_end`` are the loop thread's own note of what the handler has
    been woken for — the request's token count at the last wake, and
    whether its end was announced — so a step's wake-up skips a handle
    with no news (:meth:`CompletionServer._wake_streams`).  ``num`` is the
    ``n`` of the id ``cmpl-<n>``: the integer the request's spans share."""

    __slots__ = ("creq", "num", "woken_at", "woken_end")

    def __init__(self, rid: str, creq: CompletionRequest,
                 event: asyncio.Event, num: int = 0):
        super().__init__(rid, creq.prompt_ids, sampling=creq.sampling(),
                         priority=creq.priority, event=event,
                         slo_ms=creq.slo_ms, retryable=creq.retryable)
        self.creq = creq
        self.num = num
        self.woken_at = 0
        self.woken_end = False


class _StreamWake:
    """One replica's side of the stream hand-off (``index`` None: the
    fleet-wide sweeps of supervisor, drain and abort paths): the mark
    that a wake-up callback is posted and has not started its walk, and
    the three ``serving_stream_*`` series."""

    __slots__ = ("index", "pending", "wakes", "coalesced", "woken")

    def __init__(self, index: Optional[int], registry, labels):
        self.index = index
        self.pending = False
        self.wakes = registry.counter(
            "serving_stream_wakes_total",
            "wake-up callbacks engine threads posted to the server's "
            "loop (one a step, whatever the number of open streams)",
            **labels)
        self.coalesced = registry.counter(
            "serving_stream_wakes_coalesced_total",
            "notifies that posted nothing: the replica's last callback "
            "had not started its walk", **labels)
        self.woken = registry.counter(
            "serving_stream_handles_woken_total",
            "handler events set by those callbacks (handles with a new "
            "token, a finish or a terminal mark; every handle in a "
            "fleet-wide sweep)", **labels)


class CompletionServer:
    """HTTP frontend bound to a fleet of engine replicas.

    Accepts either a :class:`FleetRouter` (dp ≥ 1, ISSUE 6) or a bare
    :class:`EngineCore` — the latter is wrapped as a fleet of one: its
    ``serving_*`` series stay unlabeled on its own registry as before,
    with the ``serving_fleet_*`` family (a one-replica fleet) added
    alongside.  ``await start()`` spawns the engine threads and binds
    the socket; ``await shutdown()`` drains the whole fleet gracefully.
    ``registry`` defaults to the fleet's shared metrics registry, so
    ``GET /metrics`` serves per-replica-labeled ``serving_*`` series,
    the ``serving_fleet_*`` family, and whatever else the caller
    registered there."""

    def __init__(self, engine,
                 config: Optional[ServerConfig] = None, registry=None):
        self.cfg = config or ServerConfig()
        if isinstance(engine, FleetRouter):
            self.fleet = engine
            if self.cfg.max_queue != self.fleet.cfg.max_queue:
                # admission lives in the router (per-replica caps), so a
                # divergent ServerConfig.max_queue would be silently dead
                # configuration — refuse instead of letting the operator
                # believe their overload cap is enforced
                raise ValueError(
                    f"ServerConfig.max_queue={self.cfg.max_queue} but the "
                    f"fleet was built with FleetConfig.max_queue="
                    f"{self.fleet.cfg.max_queue}; admission is per-replica "
                    "and owned by the fleet — set the cap there (or pass "
                    "matching values)")
        else:
            self.fleet = FleetRouter.from_engine(
                engine, max_queue=self.cfg.max_queue)
        self.registry = (registry if registry is not None
                         else self.fleet.registry)
        self._handles: Dict[str, _Handle] = {}  # loop thread only
        # replica index (None: fleet-wide sweep) -> its hand-off state;
        # bounded by dp + 1
        self._wakes: Dict[Optional[int], _StreamWake] = {}
        self._ids = itertools.count(1)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server: Optional[asyncio.AbstractServer] = None
        self._draining = False
        self._stop = False
        self._shutdown_done: Optional[asyncio.Event] = None
        self._rejected = self.registry.counter(
            "serving_admission_rejected_total",
            "requests rejected 429 at admission (every replica saturated)")
        self.port: Optional[int] = None

    # --- single-engine compat views (dp=1 tests/tools poke these) -----------
    @property
    def engine(self) -> EngineCore:
        """Replica 0's engine — the single-engine compat surface
        (selftest / existing callers poke ``.engine.mp``, ``.engine.kv``
        ...).  A property, not a snapshot: the supervisor (ISSUE 12) may
        replace replica 0's engine wholesale on restart/quarantine."""
        return self.fleet.replicas[0].engine

    @property
    def tracer(self):
        # follows replica 0's engine like `engine` above — a snapshot
        # would pin a retired engine's tracer after a supervisor rebuild
        return self.engine.tracer

    @property
    def _engine_thread(self) -> Optional[threading.Thread]:
        return self.fleet.replicas[0].thread

    @property
    def _engine_error(self) -> Optional[str]:
        return self.fleet.replicas[0].error

    # --- lifecycle ----------------------------------------------------------
    async def start(self) -> "CompletionServer":
        self._loop = asyncio.get_running_loop()
        self._shutdown_done = asyncio.Event()
        for r in self.fleet.replicas:
            self._stream_wake(r)  # the series exist from the first scrape
        self.fleet.start(notify=self._notify)
        self._server = await asyncio.start_server(
            self._handle_conn, self.cfg.host, self.cfg.port)
        self.port = self._server.sockets[0].getsockname()[1]
        return self

    def request_shutdown(self) -> None:
        """Thread/signal-safe trigger for a graceful drain."""
        if self._loop is None or self._loop.is_closed():
            return
        self._loop.call_soon_threadsafe(
            lambda: self._loop.create_task(self.shutdown()))

    async def shutdown(self, drain_timeout: Optional[float] = None) -> None:
        """Fleet-wide graceful drain: stop admission now (``/readyz`` →
        503 instantly, router refuses), let in-flight requests finish
        until the drain deadline, abort the stragglers with TIMEOUT
        through their owning replicas, stop every engine thread, close
        the socket.  Every replica exits with zero pool occupancy.
        Idempotent; concurrent callers await the first drain."""
        if self._draining:
            await self._shutdown_done.wait()
            return
        self._draining = True
        self.fleet.begin_drain()
        deadline = time.monotonic() + (
            drain_timeout if drain_timeout is not None
            else self.cfg.drain_timeout_s)
        while self._handles and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        stragglers = list(self._handles.values())
        if stragglers:
            # drain-deadline overrun: post-mortem bundle BEFORE the
            # aborts end the stragglers' timelines (flight recorder,
            # ISSUE 8)
            self.fleet.flight.trigger(
                "drain_overrun",
                detail=f"{len(stragglers)} request(s) still in flight "
                       f"at the HTTP drain deadline")
        for h in stragglers:
            self._request_abort(h, FinishReason.TIMEOUT)
        # handlers still need loop time to flush their (aborted) responses
        flush_deadline = time.monotonic() + 5.0
        while self._handles and time.monotonic() < flush_deadline:
            await asyncio.sleep(0.01)
        self._stop = True
        await self._loop.run_in_executor(None, self.fleet.stop)
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        self._shutdown_done.set()

    async def serve_forever(self) -> None:
        await self._shutdown_done.wait()

    @property
    def ready(self) -> bool:
        # ready while ANY replica's engine thread lives: the router
        # excludes dead replicas, so a partial fleet still serves (503
        # only when the whole fleet is down or draining)
        return (self._server is not None and not self._draining
                and self.fleet.alive)

    # --- fleet bridge -------------------------------------------------------
    def _stream_wake(self, replica) -> _StreamWake:
        key = None if replica is None else replica.index
        wake = self._wakes.get(key)
        if wake is None:
            # the replica's own labels, as its engine's serving series
            # have them (none in a fleet made from a bare engine, where
            # the sweeps' unlabeled series are therefore the same ones)
            labels = {} if replica is None \
                else replica.engine.metrics.labels or {}
            wake = self._wakes.setdefault(
                key, _StreamWake(key, self.registry, labels))
        return wake

    def _notify(self, replica=None) -> None:
        """A step's news (engine threads → loop thread).  Posts at most
        ONE callback to the loop however many handles are open, and none
        while the replica's last one has not started its walk: the walk
        over the handles and every ``event.set()`` happen in
        :meth:`_wake_streams`, on the loop thread, so this thread pays
        one system call a step and never reads ``_handles``.  The
        stepping replica passes itself and only its own handles are
        looked at; ``None`` (supervisor, drain, abort sweeps) wakes
        all."""
        loop = self._loop
        if loop is None or loop.is_closed():
            return
        wake = self._stream_wake(replica)
        if wake.pending:
            wake.coalesced.inc()
            return
        wake.pending = True
        try:
            loop.call_soon_threadsafe(self._wake_streams, wake)
        except RuntimeError:
            wake.pending = False
            return  # swallow-ok: loop shut down under us — the handlers it would wake are being torn down with it
        wake.wakes.inc()

    def _wake_streams(self, wake: _StreamWake) -> None:
        """The loop thread's half of :meth:`_notify`: wake the handlers
        that have something to read — the request emitted a token since
        the handle was last woken, it finished, or the handle is
        ``done`` — and leave the others asleep.  The pending mark goes
        BEFORE the walk: a step that ends during it posts anew and is
        never lost."""
        wake.pending = False
        index, woken = wake.index, 0
        with SpanTracer.phase("server.wake", None,
                              handles=len(self._handles)):
            for h in self._handles.values():
                if index is not None:
                    r = h.replica
                    if r is None or r.index != index:
                        continue
                    req = h.req
                    n = len(req.output_tokens) if req is not None else 0
                    end = h.finished
                    if not (n > h.woken_at or (end and not h.woken_end)):
                        continue
                    # max: a re-dispatched request starts over below what
                    # its handler has already read
                    h.woken_at, h.woken_end = max(n, h.woken_at), end
                h.event.set()
                woken += 1
        if woken:
            wake.woken.inc(woken)

    def _unavailable_503(self) -> Tuple[str, Tuple]:
        """(message, extra headers) for a 503.  A draining server is
        going away (no retry hint); a fleet whose replicas are all
        momentarily down while the supervisor restarts them (ISSUE 12)
        tells the client to come back — 503 **with** ``Retry-After``,
        matching the 429 path."""
        if self._draining or self._stop:
            return "server is draining", ()
        n = self.fleet.restarting_count
        if n:
            return (f"fleet is restarting ({n} replica(s) recovering); "
                    "retry later",
                    (("Retry-After", str(self.cfg.retry_after_s)),))
        return "engine is not running", ()

    def _request_abort(self, h: _Handle, reason: FinishReason) -> None:
        h.cancel_reason = reason
        # the router's request→replica owner map sends the abort to the
        # replica that actually holds the request's blocks
        self.fleet.abort(h.rid, reason)

    # --- HTTP plumbing ------------------------------------------------------
    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        """Serve one connection: HTTP/1.1 requests are persistent by
        default (``Connection: close`` or HTTP/1.0 without an explicit
        ``keep-alive`` opts out), so this loops request → response until
        the client closes, opts out, hits the idle timeout, or switches
        to a self-delimiting response (SSE streams close the socket —
        their framing has no length)."""
        try:
            while True:
                try:
                    head = await asyncio.wait_for(
                        reader.readuntil(b"\r\n\r\n"),
                        timeout=self.cfg.keepalive_timeout_s)
                except (asyncio.TimeoutError, asyncio.IncompleteReadError,
                        asyncio.LimitOverrunError, ConnectionError):
                    return  # swallow-ok: idle timeout / client closed between requests — normal keep-alive connection end, not a fault
                if len(head) > _MAX_HEADER_BYTES:
                    await self._respond(writer, 431, error_body(
                        "headers too large"))
                    return
                lines = head.decode("latin-1").split("\r\n")
                parts = lines[0].split()
                if len(parts) != 3:
                    await self._respond(writer, 400, error_body(
                        "malformed request line"))
                    return
                method, target = parts[0].upper(), parts[1]
                version = parts[2].upper()
                headers = {}
                for ln in lines[1:]:
                    if ":" in ln:
                        k, v = ln.split(":", 1)
                        headers[k.strip().lower()] = v.strip()
                conn_hdr = headers.get("connection", "").lower()
                keep_alive = (conn_hdr != "close" if version == "HTTP/1.1"
                              else conn_hdr == "keep-alive")
                if "transfer-encoding" in headers:
                    # bodies are framed by Content-Length only; a chunked
                    # body left unread would desync the persistent stream
                    # (its bytes would parse as the next request line), so
                    # reject AND close
                    await self._respond(writer, 411, error_body(
                        "Transfer-Encoding unsupported; send "
                        "Content-Length"))
                    return
                body = b""
                clen = int(headers.get("content-length", 0) or 0)
                if clen:
                    if clen > 2 * 1024 * 1024:
                        await self._respond(writer, 413, error_body(
                            "body too large"))
                        return
                    body = await asyncio.wait_for(
                        reader.readexactly(clen), timeout=30.0)
                keep_alive = await self._dispatch(
                    method, target, body, writer, keep_alive)
                if not keep_alive:
                    return
        except (ConnectionError, asyncio.TimeoutError,
                asyncio.IncompleteReadError):
            pass  # swallow-ok: client went away; the per-request abort path already freed the engine-side work
        finally:
            try:
                writer.close()
            except Exception:
                pass  # swallow-ok: socket already dead — close() is best-effort teardown of a connection we are done with

    def _count_http(self, route: str, status: int) -> None:
        if route.startswith("/v1/requests"):
            route = "/v1/requests"  # one series for all request ids
        route = route if route in _ROUTES else "other"
        self.registry.counter(
            "serving_http_requests_total", "HTTP requests served",
            route=route, code=str(status)).inc()

    async def _respond(self, writer: asyncio.StreamWriter, status: int,
                       payload, content_type: str = "application/json",
                       extra: Tuple[Tuple[str, str], ...] = (),
                       keep_alive: bool = False) -> None:
        body = (json.dumps(payload).encode("utf-8") + b"\n"
                if isinstance(payload, dict) else payload)
        reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
                  405: "Method Not Allowed", 409: "Conflict",
                  411: "Length Required",
                  413: "Payload Too Large",
                  429: "Too Many Requests", 431: "Headers Too Large",
                  500: "Internal Server Error",
                  503: "Service Unavailable"}.get(status, "OK")
        head = [f"HTTP/1.1 {status} {reason}",
                f"Content-Type: {content_type}",
                f"Content-Length: {len(body)}",
                "Connection: keep-alive" if keep_alive
                else "Connection: close"]
        head += [f"{k}: {v}" for k, v in extra]
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode("latin-1"))
        writer.write(body)
        await writer.drain()

    async def _dispatch(self, method: str, target: str, body: bytes,
                        writer: asyncio.StreamWriter,
                        keep_alive: bool = False) -> bool:
        """Route one request; returns whether the connection stays open
        (an SSE stream always closes — its framing is delimited by EOF)."""
        path, _, query = target.partition("?")
        with self.tracer.span("http_request", cat="serving",
                              method=method, path=path) as sp:
            if path == "/healthz":
                status = 200
                await self._respond(writer, status, b"ok\n", "text/plain",
                                    keep_alive=keep_alive)
            elif path == "/readyz":
                status = 200 if self.ready else 503
                # the fleet shape rides the probe body (ISSUE 5/6): a
                # deployment that came up single-replica or single-chip
                # when the operator expected dp=N / mp=M is visible from
                # the readiness check alone
                mp = getattr(self.engine, "mp", 1)
                # a degraded numerics auditor ANNOTATES readiness but
                # never flips it (ISSUE 10): the fleet still serves —
                # the operator sees the flag on every probe and digs in
                # via /v1/debug/audit
                audit_ann = (" audit=degraded" if any(
                    r.engine.audit.degraded for r in self.fleet.replicas)
                    else "")
                # replicas the supervisor is bringing back (ISSUE 12):
                # annotated while the fleet still serves, and the WHOLE
                # body when every replica is momentarily down but
                # recovery is underway — probes can tell "restarting"
                # from "dead" (and clients get Retry-After on POSTs)
                restarting = self.fleet.restarting_count
                restart_ann = (f" restarting={restarting}" if restarting
                               else "")
                if status == 200:
                    msg = (f"ok dp={self.fleet.dp} mp={mp}{audit_ann}"
                           f"{restart_ann}\n").encode()
                elif self._draining:
                    msg = b"draining\n"
                elif restarting:
                    msg = f"restarting={restarting}\n".encode()
                else:
                    msg = b"not ready\n"
                await self._respond(writer, status, msg, "text/plain",
                                    keep_alive=keep_alive)
            elif path == "/metrics":
                status = 200
                # serving_fleet_* replica gauges refresh via the
                # registry collect hook inside prometheus_text (ISSUE
                # 14) — the same freshness the push gateway and the
                # history sampler observe
                await self._respond(writer, status,
                                    metrics_page(self.registry),
                                    PROMETHEUS_CONTENT_TYPE,
                                    keep_alive=keep_alive)
            elif path == "/v1/completions":
                if method != "POST":
                    status = 405
                    await self._respond(writer, status, error_body(
                        "use POST", "method_not_allowed"),
                        keep_alive=keep_alive)
                else:
                    status, keep_alive = await self._handle_completion(
                        body, writer, keep_alive)
            elif path == "/v1/requests" or path.startswith("/v1/requests/") \
                    or path.startswith("/v1/debug/"):
                if method != "GET":
                    status = 405
                    await self._respond(writer, status, error_body(
                        "use GET", "method_not_allowed"),
                        keep_alive=keep_alive)
                else:
                    # debug surfaces answer JSON for every outcome —
                    # unknown ids are 404 and malformed query params 400
                    # (never a 500 or a dropped connection; satellite
                    # bugfix, protocol-tested)
                    try:
                        if path.startswith("/v1/debug/"):
                            status = await self._handle_debug(
                                path, query, writer, keep_alive)
                        else:
                            status = await self._handle_requests_debug(
                                path, query, writer, keep_alive)
                    except (ConnectionError, asyncio.TimeoutError):
                        raise
                    except Exception as e:
                        status = 500
                        await self._respond(writer, status, error_body(
                            f"debug handler failed: {e}", "internal_error"),
                            keep_alive=keep_alive)
            else:
                status = 404
                await self._respond(writer, status, error_body(
                    f"no route {path!r}", "not_found"),
                    keep_alive=keep_alive)
            sp.set_attribute("status", status)
        self._count_http(path, status)
        return keep_alive

    # --- request-lifecycle debug routes (ISSUE 8) ---------------------------
    async def _handle_requests_debug(self, path: str, query: str,
                                     writer: asyncio.StreamWriter,
                                     keep_alive: bool) -> int:
        """``GET /v1/requests?state=active|recent`` (timeline summaries)
        and ``GET /v1/requests/{id}[?format=chrome]`` (one request's full
        timeline, or its per-request Chrome trace)."""
        import urllib.parse

        params = urllib.parse.parse_qs(query)
        lc = self.fleet.lifecycle
        source, complete = self._timeline_source()
        if path == "/v1/requests":
            state = params.get("state", ["active"])[0]
            if state not in ("active", "recent"):
                await self._respond(writer, 400, error_body(
                    "state must be 'active' or 'recent'"),
                    keep_alive=keep_alive)
                return 400
            await self._respond(
                writer, 200,
                {"object": "list", "state": state,
                 "source": source, "complete": complete,
                 "data": lc.summaries(state)},
                keep_alive=keep_alive)
            return 200
        rid = urllib.parse.unquote(path[len("/v1/requests/"):])
        fmt = params.get("format", [None])[0]
        if fmt not in (None, "json", "chrome"):
            # invalid query param: a crisp JSON 400, not a silently
            # ignored knob (satellite bugfix)
            await self._respond(writer, 400, error_body(
                f"format must be 'json' or 'chrome', got {fmt!r}"),
                keep_alive=keep_alive)
            return 400
        tl = lc.get(rid)
        if tl is None:
            await self._respond(writer, 404, error_body(
                f"no timeline for request {rid!r} (it may have aged out "
                "of the recent ring)", "not_found"),
                keep_alive=keep_alive)
            return 404
        if fmt == "chrome":
            # build from the timeline already in hand — a second lookup
            # could miss (the recent ring is bounded) and return None
            from ..observability.export import chrome_trace_dict

            payload = chrome_trace_dict(tl.chrome_spans(),
                                        epoch_offset=lc.epoch_offset)
        else:
            payload = dict(tl.to_dict(lc.epoch_offset), object="request",
                           source=source, complete=complete)
        await self._respond(writer, 200, payload, keep_alive=keep_alive)
        return 200

    def _timeline_source(self) -> Tuple[str, bool]:
        """Honesty marker for the timeline endpoints (ISSUE 17
        satellite): in ``--workers`` mode WITHOUT telemetry streaming
        the router's tracker holds router-synthesized stand-ins only, so
        the response must say ``complete: false`` instead of presenting
        a router-only view as the whole story."""
        proxies = [r.engine for r in self.fleet.replicas
                   if hasattr(r.engine, "distrib_state")]
        if not proxies:
            return "in-process", True
        if all(getattr(p, "_telemetry", False) for p in proxies):
            return "router+workers", True
        return "router-only", False

    # --- step-level introspection routes (ISSUE 9) --------------------------
    def _debug_int(self, params, name: str, default: int,
                   lo: int, hi: int) -> int:
        """Parse an integer query param in [lo, hi]; raises ValueError
        with an operator-readable message (mapped to a JSON 400)."""
        raw = params.get(name, [None])[0]
        if raw is None:
            return default
        try:
            v = int(raw)
        except ValueError:
            raise ValueError(
                f"{name} must be an integer, got {raw!r}") from None
        if not lo <= v <= hi:
            raise ValueError(f"{name} must be in [{lo}, {hi}], got {v}")
        return v

    def _replica_rows(self, reps, fetch) -> List[Dict]:
        """Per-replica debug rows with mid-restart degradation (ISSUE 16
        satellite bugfix): a replica that is being rebuilt/respawned —
        unhealthy, or whose snapshot fetch fails during the engine swap
        / worker respawn window — contributes a
        ``{"status": "restarting"}`` row instead of 404/500-ing the
        whole endpoint.  Debug surfaces stay useful DURING incidents,
        which is exactly when operators hit them."""
        rows = []
        for r in reps:
            if not r.healthy:
                rows.append({"replica": str(r.index), "enabled": False,
                             "status": "restarting"})
                continue
            try:
                rows.append(dict(fetch(r), replica=str(r.index)))
            except Exception:
                rows.append({"replica": str(r.index), "enabled": False,
                             "status": "restarting"})
        return rows

    async def _handle_debug(self, path: str, query: str,
                            writer: asyncio.StreamWriter,
                            keep_alive: bool) -> int:
        """``GET /v1/debug/compiles`` — per-replica compile-time
        attribution table (every observed trace+compile with its wall
        seconds); ``GET /v1/debug/profile?steps=N[&replica=i]`` — arm a
        bounded capture window on the replica's StepProfiler, wait for
        the next N engine steps, answer the annotated Chrome trace."""
        import urllib.parse

        from ..observability.stepprof import CaptureBusy

        params = urllib.parse.parse_qs(query)
        if path == "/v1/debug/audit":
            # numerics-audit status (ISSUE 10): per-replica auditor
            # snapshots (counters, last divergence, repro paths) plus a
            # fleet-level status roll-up — "ok" only when every enabled
            # auditor is clean, "degraded" the moment any diverged,
            # "disabled" when no replica audits
            try:
                replica = self._debug_int(params, "replica", -1,
                                          -1, 1 << 30)
            except ValueError as e:
                await self._respond(writer, 400, error_body(str(e)),
                                    keep_alive=keep_alive)
                return 400
            if replica >= self.fleet.dp:
                await self._respond(writer, 404, error_body(
                    f"no replica {replica} (fleet has dp="
                    f"{self.fleet.dp})", "not_found"),
                    keep_alive=keep_alive)
                return 404
            reps = (self.fleet.replicas if replica < 0
                    else [self.fleet.replicas[replica]])
            data = self._replica_rows(
                reps, lambda r: r.engine.audit.snapshot())
            enabled = [d for d in data if d.get("enabled")]
            status = ("disabled" if not enabled else
                      "degraded" if any(d.get("status") == "degraded"
                                        for d in enabled) else "ok")
            await self._respond(
                writer, 200,
                {"object": "list", "status": status, "data": data},
                keep_alive=keep_alive)
            return 200
        if path == "/v1/debug/cache":
            # KV-cache & memory observability (ISSUE 13): per-replica
            # pool timelines, prefix-heat tables, hit-depth/eviction
            # reports and per-request attribution, plus a fleet view —
            # per-replica cached-token ratios and the max−min imbalance
            # (the cache-aware rebalancing signal)
            try:
                replica = self._debug_int(params, "replica", -1,
                                          -1, 1 << 30)
            except ValueError as e:
                await self._respond(writer, 400, error_body(str(e)),
                                    keep_alive=keep_alive)
                return 400
            if replica >= self.fleet.dp:
                await self._respond(writer, 404, error_body(
                    f"no replica {replica} (fleet has dp="
                    f"{self.fleet.dp})", "not_found"),
                    keep_alive=keep_alive)
                return 404
            reps = (self.fleet.replicas if replica < 0
                    else [self.fleet.replicas[replica]])
            data = self._replica_rows(
                reps, lambda r: r.engine.cachestat.snapshot())
            # ONE ratio snapshot: the body's imbalance is derived from
            # the very ratios it reports, so the two fields can never
            # disagree under concurrent traffic
            ratios = self.fleet.cached_token_ratios()
            vals = [v for v in ratios.values() if v is not None]
            imbalance = max(vals) - min(vals) if vals else None
            self.fleet.sample_gauges()  # the imbalance gauge tracks it
            await self._respond(
                writer, 200,
                {"object": "list",
                 "status": ("ok" if any(d.get("enabled") for d in data)
                            else "disabled"),
                 "fleet": {
                     "dp": self.fleet.dp,
                     "cached_token_ratios": {
                         k: (None if v is None else round(v, 4))
                         for k, v in ratios.items()},
                     "cache_imbalance": (None if imbalance is None
                                         else round(imbalance, 4)),
                 },
                 "data": data},
                keep_alive=keep_alive)
            return 200
        if path == "/v1/debug/alerts":
            # alert-engine state (ISSUE 14): every rule with its live
            # pending/firing state + recent transitions, plus engine
            # totals; ?rule= filters to one rule (unknown -> 404)
            alerts = self.fleet.alerts
            if alerts is None:
                await self._respond(
                    writer, 200,
                    {"object": "alerts", "status": "disabled",
                     "rules": 0, "data": []}, keep_alive=keep_alive)
                return 200
            snap = alerts.snapshot()
            rule = params.get("rule", [None])[0]
            if rule is not None:
                rows = [d for d in snap["data"]
                        if d["rule"]["name"] == rule]
                if not rows:
                    await self._respond(writer, 404, error_body(
                        f"no alert rule {rule!r}", "not_found"),
                        keep_alive=keep_alive)
                    return 404
                # scope status + firing to the queried rule: an
                # operator asking about an inactive rule must not read
                # "firing" off some OTHER rule's incident
                snap = dict(snap, data=rows, firing=[
                    d["rule"]["name"] for d in rows
                    if d["state"] == "firing"])
            status = ("firing" if snap["firing"] else "ok")
            await self._respond(
                writer, 200,
                dict({"object": "alerts", "status": status}, **snap),
                keep_alive=keep_alive)
            return 200
        if path == "/v1/debug/history":
            # metrics history (ISSUE 14): ?series=<metric name> answers
            # the per-label-set windows (per-replica view) plus a fleet
            # aggregate; without ?series= the series index is returned.
            # ?window=N bounds the returned samples (malformed -> 400,
            # unknown series -> 404 — protocol-clean like /v1/debug/cache)
            history = self.fleet.history
            if history is None:
                await self._respond(
                    writer, 200,
                    {"object": "history", "status": "disabled",
                     "data": []}, keep_alive=keep_alive)
                return 200
            try:
                window = self._debug_int(params, "window",
                                         history.cfg.ring_len, 1,
                                         history.cfg.ring_len)
            except ValueError as e:
                await self._respond(writer, 400, error_body(str(e)),
                                    keep_alive=keep_alive)
                return 400
            series = params.get("series", [None])[0]
            if series is None:
                await self._respond(
                    writer, 200,
                    {"object": "history", "status": "ok",
                     "stats": history.stats(),
                     "series": history.names()}, keep_alive=keep_alive)
                return 200
            keys = history.match(series)
            if not keys:
                await self._respond(writer, 404, error_body(
                    f"no recorded series {series!r} (see "
                    "/v1/debug/history for the index)", "not_found"),
                    keep_alive=keep_alive)
                return 404
            rows = [{"key": k, "kind": history.kind(k),
                     "latest": history.latest(k),
                     "window": history.window(k, window)}
                    for k in keys]
            fleet_view = {"latest_sum": history.name_latest_sum(series)}
            if all(r["kind"] == "counter" for r in rows):
                fleet_view["increase"] = history.name_increase(
                    series, window)
            await self._respond(
                writer, 200,
                {"object": "history", "status": "ok", "series": series,
                 "window": window, "fleet": fleet_view, "data": rows},
                keep_alive=keep_alive)
            return 200
        if path == "/v1/debug/compiles":
            data = []
            totals: Dict[str, Dict] = {}
            aot: Dict[str, Dict] = {}
            for r in self.fleet.replicas:
                if not r.healthy:
                    # mid-restart replica (ISSUE 16 satellite): degrade
                    # its slot instead of failing the fleet-wide table
                    aot[str(r.index)] = {"status": "restarting"}
                    continue
                try:
                    sp = r.engine.stepprof
                    rows = [dict(row, replica=str(r.index))
                            for row in sp.compile_table()]
                    tots = list(sp.compile_totals().items())
                    # AOT attribution (ISSUE 15): per-replica artifact
                    # state — with an artifact loaded the rows above
                    # should be EMPTY (any row carries aot: true, the
                    # bug marker)
                    aot[str(r.index)] = sp.aot_snapshot()
                except Exception:
                    aot[str(r.index)] = {"status": "restarting"}
                    continue
                data.extend(rows)
                for prog, t in tots:
                    agg = totals.setdefault(
                        prog, {"seconds": 0.0, "count": 0})
                    agg["seconds"] = round(agg["seconds"] + t["seconds"], 6)
                    agg["count"] += t["count"]
            await self._respond(
                writer, 200,
                {"object": "list", "data": data, "totals": totals,
                 "aot": aot,
                 "step_profile": self.engine.stepprof.enabled},
                keep_alive=keep_alive)
            return 200
        if path == "/v1/debug/wire":
            # ISSUE 17: per-worker wire-latency attribution + clock-sync
            # + telemetry-merge state.  In-process fleets answer a crisp
            # "disabled" shape (there is no wire), mirroring the other
            # debug endpoints' degrade-not-404 discipline.
            rows: Dict[str, Dict] = {}
            for r in self.fleet.replicas:
                eng = r.engine
                if not hasattr(eng, "distrib_state"):
                    continue
                try:
                    rows[str(r.index)] = eng.distrib_state()
                except Exception:
                    rows[str(r.index)] = {"status": "restarting"}
            if not rows:
                await self._respond(
                    writer, 200,
                    {"object": "wire", "enabled": False,
                     "reason": "in-process fleet: no process wire to "
                               "attribute (use --workers)"},
                    keep_alive=keep_alive)
                return 200
            from ..observability.distrib import WireStats
            agg = {"steps": 0, "wire_s": 0.0, "queue_s": 0.0,
                   "engine_s": 0.0, "total_s": 0.0}
            for state in rows.values():
                w = state.get("wire") or {}
                for k in agg:
                    agg[k] += w.get(k, 0) or 0
            await self._respond(
                writer, 200,
                {"object": "wire", "enabled": True,
                 "shares": WireStats._shares(agg),
                 "steps": agg["steps"],
                 "replicas": rows},
                keep_alive=keep_alive)
            return 200
        if path != "/v1/debug/profile":
            await self._respond(writer, 404, error_body(
                f"no route {path!r}", "not_found"),
                keep_alive=keep_alive)
            return 404
        try:
            timeout_s = self._debug_int(params, "timeout_s", 30, 1, 300)
            replica = self._debug_int(params, "replica", 0,
                                      0, 1 << 30)
        except ValueError as e:
            await self._respond(writer, 400, error_body(str(e)),
                                keep_alive=keep_alive)
            return 400
        if replica >= self.fleet.dp:
            # an unknown id is a 404, not a malformed request
            await self._respond(writer, 404, error_body(
                f"no replica {replica} (fleet has dp={self.fleet.dp})",
                "not_found"), keep_alive=keep_alive)
            return 404
        sp = self.fleet.replicas[replica].engine.stepprof
        try:
            # bound against the TARGET profiler's own cap — one limit,
            # owned by arm_capture, never duplicated here
            steps = self._debug_int(params, "steps", 32, 1,
                                    sp.max_capture_steps)
            # in an executor: on a real device arming starts the JAX
            # profiler, which takes seconds — the event loop (every
            # stream, every new request) must not wait for it, nor
            # does the engine (arm_capture starts it outside the lock
            # the step takes)
            window = await self._loop.run_in_executor(
                None, sp.arm_capture, steps)
        except CaptureBusy as e:
            await self._respond(writer, 409, error_body(
                str(e), "conflict"), keep_alive=keep_alive)
            return 409
        except (RuntimeError, ValueError) as e:
            # step_profile disabled, or a steps value the profiler's
            # own validation refuses — either way a client error
            await self._respond(writer, 400, error_body(str(e)),
                                keep_alive=keep_alive)
            return 400
        try:
            deadline = time.monotonic() + timeout_s
            while not window.done.is_set() \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            if not window.done.is_set():
                # idle/slow engine: return what the window captured so
                # far (``complete: false``) instead of hanging.  The
                # finalize runs in an executor — a device stop_trace
                # flushing its XPlane dump must not stall the event
                # loop — and may lose to a concurrent engine-side
                # finalize, so keep polling ``done`` afterwards: never
                # read a half-built result
                await self._loop.run_in_executor(
                    None, sp.cancel_capture, window)
                grace = time.monotonic() + 30.0
                while not window.done.is_set() \
                        and time.monotonic() < grace:
                    await asyncio.sleep(0.01)
            if window.result is None:
                await self._respond(writer, 503, error_body(
                    "capture window did not finalize in time",
                    "unavailable_error"), keep_alive=keep_alive)
                return 503
            await self._respond(writer, 200, window.result,
                                keep_alive=keep_alive)
            return 200
        finally:
            # the handler task can die mid-wait (client disconnect,
            # CancelledError on shutdown): an armed window left behind
            # would 409 every future capture — and on device leave
            # jax.profiler tracing.  No-op when already finalized; runs
            # on its own thread so a slow device stop_trace never
            # stalls the event loop (and cancellation can't skip it).
            threading.Thread(target=sp.cancel_capture, args=(window,),
                             daemon=True).start()

    # --- the completions route ----------------------------------------------
    async def _handle_completion(self, body: bytes,
                                 writer: asyncio.StreamWriter,
                                 keep_alive: bool = False,
                                 ) -> Tuple[int, bool]:
        """Returns (status, connection-still-open)."""
        unavailable_msg, unavailable_extra = self._unavailable_503()
        if not self.ready:
            # draining OR every engine thread died: either way nobody
            # will ever drain a submit queue, so refuse instead of
            # hanging.  A fleet mid-restart (ISSUE 12) answers with
            # Retry-After — the outage is transient by construction.
            await self._respond(writer, 503, error_body(
                unavailable_msg, "unavailable_error"),
                extra=unavailable_extra, keep_alive=keep_alive)
            return 503, keep_alive
        try:
            creq = parse_completion_request(body, tokenize=self.cfg.tokenize)
        except ProtocolError as e:
            await self._respond(writer, 400, error_body(str(e)),
                                keep_alive=keep_alive)
            return 400, keep_alive

        # two admission layers: the router's per-replica caps bound
        # ENGINE-side work (evicted as requests finish computing), while
        # this server-wide cap bounds HTTP-side work — handles, sockets,
        # buffered output still flushing to slow clients — which can
        # outlive the engine's interest in a request
        if len(self._handles) >= self.cfg.max_queue * self.fleet.dp:
            self._rejected.inc()
            self.fleet.flight.note_rejection()
            await self._respond(
                writer, 429,
                error_body("admission queue is full; retry later",
                           "overloaded_error"),
                extra=(("Retry-After", str(self.cfg.retry_after_s)),),
                keep_alive=keep_alive)
            return 429, keep_alive
        # router admission is per replica: prefix-affinity target first,
        # least-loaded fallback; 429 only when EVERY eligible replica is
        # at its in-flight cap
        num = next(self._ids)
        refused = None
        # the loop thread's synchronous stretch for an accepted request,
        # no await inside: the handle, the router's placement, the queue
        with SpanTracer.phase("server.accept", None, req=num,
                              prompt_tokens=len(creq.prompt_ids)):
            rid = f"cmpl-{num}"
            handle = _Handle(rid, creq, asyncio.Event(), num)
            try:
                self.fleet.submit(handle)
            except (FleetSaturated, FleetDown) as e:
                refused = e  # swallow-ok: answered 429 / 503 just below, outside the span (no await may sit inside it)
            else:
                self._handles[rid] = handle
        if isinstance(refused, FleetSaturated):
            self._rejected.inc()
            self.fleet.flight.note_rejection()
            await self._respond(
                writer, 429,
                error_body("admission queue is full; retry later",
                           "overloaded_error"),
                extra=(("Retry-After", str(self.cfg.retry_after_s)),),
                keep_alive=keep_alive)
            return 429, keep_alive
        if refused is not None:
            unavailable_msg, unavailable_extra = self._unavailable_503()
            await self._respond(writer, 503, error_body(
                unavailable_msg, "unavailable_error"),
                extra=unavailable_extra, keep_alive=keep_alive)
            return 503, keep_alive

        timeout = creq.timeout if creq.timeout is not None \
            else self.cfg.default_timeout_s
        if timeout is not None:
            timeout = min(float(timeout), self.cfg.max_timeout_s)
        try:
            if creq.stream:
                status = await self._stream_response(handle, timeout, writer)
                return status, False  # SSE framing is delimited by EOF
            status = await self._json_response(handle, timeout, writer,
                                               keep_alive)
            return status, keep_alive
        except (ConnectionError, asyncio.TimeoutError):
            # client vanished mid-response: free the engine-side work
            self._request_abort(handle, FinishReason.ABORT)
            raise
        finally:
            self._handles.pop(rid, None)

    async def _collect(self, handle: _Handle, timeout: Optional[float],
                       on_tokens=None) -> Tuple[List[int], str]:
        """Wait on the engine until ``handle``'s request finishes (or its
        deadline aborts it); returns (tokens, finish_reason).  Streaming
        passes ``on_tokens`` to flush each batch as it lands."""
        deadline = None if timeout is None else time.monotonic() + timeout
        tokens: List[int] = []
        cursor = 0
        while True:
            req = handle.req
            if req is not None:
                out = req.output_tokens
                if cursor < len(out):
                    new = out[cursor:]
                    cursor = len(out)
                    tokens.extend(new)
                    if on_tokens is not None:
                        await on_tokens(new)
                if req.finished and cursor == len(req.output_tokens):
                    reason = (req.finish_reason.value
                              if req.finish_reason else "abort")
                    return tokens, reason
            if handle.done and (req is None or not req.finished):
                # terminal without an engine finish: cancelled before
                # admission, or the owning replica died and the
                # supervisor closed the handle (ISSUE 12 — ``req`` may
                # still hold the dead engine's frozen partial output,
                # flushed above)
                reason = (handle.cancel_reason.value
                          if handle.cancel_reason else "abort")
                return tokens, reason
            if deadline is not None and time.monotonic() >= deadline:
                # propagate the deadline into the scheduler, then keep
                # waiting (deadline-free) for the engine to acknowledge
                # so the partial output below is consistent
                self._request_abort(handle, FinishReason.TIMEOUT)
                deadline = None
                continue
            wait = _POLL_S if deadline is None \
                else max(0.0, min(_POLL_S, deadline - time.monotonic()))
            try:
                await asyncio.wait_for(handle.event.wait(), wait + 1e-3)
            except asyncio.TimeoutError:
                continue  # swallow-ok: the wait IS a poll; timeout means re-check request state, not a fault
            handle.event.clear()

    @staticmethod
    def _prompt_cached(handle: _Handle) -> int:
        """Cached prompt tokens at the request's first admission (the
        usage attribution, ISSUE 13); 0 when never admitted."""
        cached = getattr(handle.req, "prompt_cached_tokens", None)
        return int(cached or 0)

    async def _json_response(self, handle: _Handle,
                             timeout: Optional[float],
                             writer: asyncio.StreamWriter,
                             keep_alive: bool = False) -> int:
        tokens, reason = await self._collect(handle, timeout)
        req = handle.req
        await self._respond(writer, 200, completion_body(
            handle.rid, self.cfg.model_name, tokens, reason,
            len(handle.creq.prompt_ids),
            error=getattr(req, "error", None),
            prompt_cached_tokens=self._prompt_cached(handle)),
            extra=(("X-Request-Id", handle.rid),), keep_alive=keep_alive)
        return 200

    async def _stream_response(self, handle: _Handle,
                               timeout: Optional[float],
                               writer: asyncio.StreamWriter) -> int:
        # every synchronous stretch that builds a chunk and hands it to
        # the socket is one ``server.write`` span (never the drain, which
        # gives the loop away)
        phase, num = SpanTracer.phase, handle.num
        with phase("server.write", None, req=num, tokens=0):
            writer.write(b"HTTP/1.1 200 OK\r\n"
                         b"Content-Type: text/event-stream\r\n"
                         b"Cache-Control: no-store\r\n"
                         + f"X-Request-Id: {handle.rid}\r\n".encode("latin-1")
                         + b"Connection: close\r\n\r\n")
            # id-bearing FIRST chunk, before any token exists: an SSE
            # client learns the request id immediately (for
            # /v1/requests/{id} or an out-of-band abort) instead of only
            # once the first token lands
            writer.write(sse_event(chunk_body(
                handle.rid, self.cfg.model_name, [], None)))
        await writer.drain()

        async def on_tokens(new: List[int]) -> None:
            with phase("server.write", None, req=num, tokens=len(new)):
                writer.write(sse_event(chunk_body(
                    handle.rid, self.cfg.model_name, new, None)))
            await writer.drain()

        tokens, reason = await self._collect(handle, timeout, on_tokens)
        # the FINAL chunk carries the usage block — SSE clients see the
        # prefix-cache attribution too (ISSUE 13 satellite)
        with phase("server.write", None, req=num, tokens=0):
            writer.write(sse_event(chunk_body(
                handle.rid, self.cfg.model_name, [], reason,
                usage=usage_body(len(handle.creq.prompt_ids), len(tokens),
                                 self._prompt_cached(handle)))))
            writer.write(SSE_DONE)
        await writer.drain()
        return 200


# --- CLI / selftest ---------------------------------------------------------

def _toy_engine(layers: int = 2, num_blocks: int = 64,
                block_size: int = 4, registry=None,
                metrics_labels=None, audit=None,
                unified: bool = False, aot=None,
                max_tokens_per_step: Optional[int] = None,
                spec=None, burst_steps: int = 0,
                role: str = "unified") -> EngineCore:
    import paddle_tpu as paddle
    from ..models import LlamaConfig, LlamaForCausalLM
    from .engine import EngineConfig
    from .scheduler import SchedulerConfig

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))
    scheduler = None
    if max_tokens_per_step is not None:
        scheduler = SchedulerConfig(
            max_tokens_per_step=int(max_tokens_per_step))
    return EngineCore(model,
                      config=EngineConfig(num_blocks=num_blocks,
                                          block_size=block_size,
                                          audit=audit,
                                          unified_step=unified,
                                          scheduler=scheduler,
                                          spec=spec,
                                          burst_steps=burst_steps,
                                          aot=aot,
                                          role=role),
                      registry=registry, metrics_labels=metrics_labels)


def _toy_fleet(dp: int = 1, layers: int = 2, num_blocks: int = 64,
               max_queue: int = 64,
               flight_dir: Optional[str] = None,
               audit=None, unified: bool = False,
               fault_plan=None, alert_rules=None,
               aot=None, max_tokens_per_step: Optional[int] = None,
               spec=None, burst_steps: int = 0,
               roles=None) -> FleetRouter:
    """A dp-replica fleet of toy engines on one shared registry: each
    replica gets its OWN model instance (engine threads swap parameter
    values during the traced step — modules must not be shared) with
    per-replica-labeled serving series.  Composes with ``--mp``: build
    the mesh first and every replica's engine runs mesh-spanning.  The
    factory is deterministic (seed before build), so the supervisor can
    rebuild a crashed replica with identical weights.  ``aot`` is ONE
    loaded :class:`~paddle_tpu.serving.aot.AotArtifact` shared by every
    replica (ISSUE 15) — the fleet refuses per-replica loads."""
    return FleetRouter.build(
        lambda i, registry: _toy_engine(
            layers=layers, num_blocks=num_blocks, registry=registry,
            metrics_labels={"replica": str(i)}, audit=audit,
            unified=unified, aot=aot,
            max_tokens_per_step=max_tokens_per_step, spec=spec,
            burst_steps=burst_steps,
            role=(roles[i] if roles else "unified")),
        dp=dp, config=FleetConfig(max_queue=max_queue,
                                  flight_dir=flight_dir,
                                  fault_plan=fault_plan,
                                  alert_rules=alert_rules,
                                  roles=roles))


def _http(port: int, method: str, path: str, body: Optional[dict] = None):
    """Blocking loopback request (runs in an executor under asyncio)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    payload = None if body is None else json.dumps(body)
    conn.request(method, path, payload,
                 {"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    data = resp.read()
    status = resp.status
    conn.close()
    return status, data


async def _selftest_async(dp: int = 1, audit_sample: int = 1,
                          unified: bool = False,
                          aot_path: Optional[str] = None,
                          layers: int = 2, blocks: int = 64) -> int:
    from ..observability.audit import AuditConfig

    loop = asyncio.get_running_loop()
    # the selftest always exercises the numerics-audit surface (ISSUE
    # 10): every step sampled by default, so the probe completion runs
    # with the shadow oracle live and must come back divergence-free.
    # --unified routes the probe through the packed ragged step program
    # (ISSUE 11) under the same audit net.  --aot-path loads the saved
    # program set ONCE and the probe must then serve with ZERO traces
    # (ISSUE 15; the audit net stays live — the in-trace logit stats
    # are part of the exported programs).
    aot = None
    if aot_path:
        from .aot import AotArtifact

        aot = AotArtifact.load(aot_path)
    fleet = _toy_fleet(dp=dp, layers=layers, num_blocks=blocks,
                       audit=AuditConfig(
                           enabled=True,
                           sample_every=max(1, audit_sample)),
                       unified=unified, aot=aot)
    server = CompletionServer(fleet, ServerConfig(port=0))
    engine = server.engine
    await server.start()
    try:
        status, data = await loop.run_in_executor(
            None, _http, server.port, "GET", "/readyz", None)
        assert status == 200, f"/readyz {status}"
        # readiness must report the fleet shape (ISSUE 5/6): a deployment
        # that came up single-replica or single-chip when the operator
        # expected dp=N / mp=M is visible from the probe body alone
        assert f"dp={fleet.dp} mp={engine.mp}".encode() in data, \
            f"/readyz body missing fleet shape: {data!r}"
        status, data = await loop.run_in_executor(
            None, _http, server.port, "POST", "/v1/completions",
            {"prompt": [5, 9, 23, 7], "max_tokens": 4})
        assert status == 200, f"completions {status}: {data!r}"
        obj = json.loads(data)
        choice = obj["choices"][0]
        assert len(choice["token_ids"]) == 4, choice
        assert choice["finish_reason"] == "length", choice
        # lifecycle debug surface (ISSUE 8): the completion's timeline is
        # queryable after it finished
        status, data = await loop.run_in_executor(
            None, _http, server.port, "GET", "/v1/requests?state=recent",
            None)
        assert status == 200, f"/v1/requests {status}"
        rows = json.loads(data)["data"]
        assert any(row["id"] == obj["id"] for row in rows), \
            f"finished completion missing from /v1/requests: {rows}"
        status, data = await loop.run_in_executor(
            None, _http, server.port, "GET", "/metrics", None)
        assert status == 200 and b"serving_time_to_first_token" in data, \
            "metrics page missing serving histograms"
        assert b"serving_e2e_seconds" in data, \
            "metrics page missing the SLO breakdown histograms"
        assert b"serving_mp_shards" in data, \
            "metrics page missing the mp-shards gauge"
        # the probe went through the router: fleet series must exist and
        # exactly one routing counter must have counted it
        assert b"serving_fleet_replicas" in data, \
            "metrics page missing the serving_fleet_* family"
        routed = sum(fleet.routing_counts.values())
        assert routed >= 1, "completion did not route through the fleet"
        # numerics-audit surface (ISSUE 10): the completion ran under
        # sample_every=1, so at least one step was shadow-audited with
        # zero divergences and the debug endpoint reports ok
        assert b"serving_audit_steps_total" in data, \
            "metrics page missing the serving_audit_* family"
        status, data = await loop.run_in_executor(
            None, _http, server.port, "GET", "/v1/debug/audit", None)
        assert status == 200, f"/v1/debug/audit {status}"
        audit = json.loads(data)
        assert audit["status"] == "ok", audit
        audited = sum(sum(row["audited_launches"].values())
                      for row in audit["data"])
        assert audited > 0, f"no audited step launches: {audit}"
        assert all(sum(row["divergences"].values()) == 0
                   for row in audit["data"]), audit
        # a crashed shadow oracle must not pass as "audited clean"
        assert all(row["oracle_failures"] == 0
                   for row in audit["data"]), audit
        if aot is not None:
            # zero-trace contract (ISSUE 15): the probe served entirely
            # from the loaded artifact — no engine traced anything
            traces = sum(e.prefill_trace_count + e.decode_trace_count
                         + e.ragged_trace_count for e in fleet.engines)
            assert traces == 0, \
                f"AOT selftest traced {traces} program(s)"
            status, data = await loop.run_in_executor(
                None, _http, server.port, "GET", "/v1/debug/compiles",
                None)
            obj = json.loads(data)
            assert status == 200 and not obj["data"], obj
            assert all(row["loaded"] for row in obj["aot"].values()), obj
        print(f"selftest: OK (port {server.port}, dp={fleet.dp}, "
              f"mp={engine.mp}, tokens {choice['token_ids']}, "
              f"audited launches {audited}"
              + (f", aot programs {aot.program_count}, zero traces"
                 if aot is not None else "") + ")")
        return 0
    finally:
        await server.shutdown(drain_timeout=2.0)


def _spec_dict(args) -> Optional[dict]:
    """SpecConfig kwargs from the CLI (``None`` = spec decoding off)."""
    if not getattr(args, "spec_decode", False):
        return None
    return {"enabled": True, "k": args.spec_k}


def _build_procfleet(args, fault_plan=None, alert_rules=None):
    # cross-process fleet (ISSUE 16): N worker processes behind the
    # SAME router/supervisor stack, reached over the wire protocol.
    # The router process never loads program bytes — workers boot
    # off the shared artifact themselves (--aot-path is forwarded)
    from .procfleet import ProcessFleet, ProcessFleetConfig

    pf = ProcessFleet(ProcessFleetConfig(
        dp=args.workers, layers=args.layers, num_blocks=args.blocks,
        max_num_seqs=8, max_prefill_tokens_per_step=None,
        max_tokens_per_step=args.max_tokens_per_step,
        # multi-chip workers (ISSUE 18): each worker process builds its
        # own mp-way mesh slice; the degree (and the spec-decoding
        # config) is validated at every wire handshake
        mp=args.mp, spec=_spec_dict(args),
        burst_steps=args.burst,
        unified=args.unified,
        audit_enabled=bool(args.audit_sample),
        audit_sample_every=args.audit_sample or 1,
        aot_path=args.aot_path, warm_boot=args.aot_warm,
        roles=getattr(args, "roles_list", None),
        fleet=FleetConfig(max_queue=args.max_queue,
                          flight_dir=args.flight_dir,
                          fault_plan=fault_plan,
                          alert_rules=alert_rules)))
    # ISSUE 17 satellite: the SLO actuators are now one flag away on the
    # serving CLI instead of library-only calls
    if getattr(args, "autoscale", False):
        from .procfleet import AutoscalerConfig

        pf.enable_autoscaler(AutoscalerConfig(
            min_replicas=args.autoscale_min,
            max_replicas=args.autoscale_max))
        print(f"autoscaler: live (min={pf.autoscaler.min_replicas}, "
              f"max={pf.autoscaler.max_replicas})")
    if getattr(args, "rebalance", False):
        pf.enable_rebalancer()
        print("rebalancer: live")
    return pf


async def _selftest_procfleet_async(args) -> int:
    loop = asyncio.get_running_loop()
    pf = _build_procfleet(args)
    fleet = pf.router
    server = CompletionServer(fleet, ServerConfig(
        port=0, max_queue=args.max_queue))
    await server.start()
    try:
        status, data = await loop.run_in_executor(
            None, _http, server.port, "POST", "/v1/completions",
            {"prompt": [5, 9, 23, 7], "max_tokens": 4})
        assert status == 200, f"completions {status}: {data!r}"
        obj = json.loads(data)
        choice = obj["choices"][0]
        assert len(choice["token_ids"]) == 4, choice
        # honesty markers (ISSUE 17 satellite): --workers mode with
        # telemetry streaming answers /v1/requests with the full
        # cross-process story
        status, data = await loop.run_in_executor(
            None, _http, server.port, "GET", "/v1/requests?state=recent",
            None)
        assert status == 200, f"/v1/requests {status}"
        listing = json.loads(data)
        assert listing.get("source") == "router+workers", listing
        assert listing.get("complete") is True, listing
        # wire-latency attribution is queryable after one completion
        status, data = await loop.run_in_executor(
            None, _http, server.port, "GET", "/v1/debug/wire", None)
        assert status == 200, f"/v1/debug/wire {status}"
        wire = json.loads(data)
        assert wire["enabled"] and wire["steps"] >= 1, wire
        if args.autoscale:
            assert pf.autoscaler is not None \
                and pf.autoscaler._thread.is_alive(), \
                "autoscaler actuator thread is not live"
        print(f"selftest: OK (port {server.port}, workers={args.workers},"
              f" tokens {choice['token_ids']}, wire steps "
              f"{wire['steps']}"
              + (", autoscaler live" if args.autoscale else "") + ")")
        return 0
    finally:
        await server.shutdown(drain_timeout=2.0)
        pf.shared.close_all()


async def _serve_cli(args) -> int:
    audit = None
    if args.audit_sample:
        from ..observability.audit import AuditConfig

        audit = AuditConfig(enabled=True, sample_every=args.audit_sample)
    fault_plan = None
    if args.fault_plan:
        from .faultinject import FaultPlan

        fault_plan = FaultPlan.from_json(args.fault_plan)
    alert_rules = None
    if args.alert_rules:
        from ..observability.alerts import AlertRuleSet

        alert_rules = AlertRuleSet.from_json(args.alert_rules)
    pf = None
    if args.workers:
        pf = _build_procfleet(args, fault_plan=fault_plan,
                              alert_rules=alert_rules)
        fleet = pf.router
        for i in range(args.workers):
            print(f"worker {i}: pid {pf.worker_pid(i)}")
    else:
        aot = None
        if args.aot_path:
            # ONE load for the whole fleet (ISSUE 15): every replica —
            # and every supervisor rebuild — shares this artifact's
            # compiled executables, so each program compiles once per
            # process
            from .aot import AotArtifact

            aot = AotArtifact.load(args.aot_path)
            print(f"aot: loaded {aot.program_count} program(s) from "
                  f"{args.aot_path} in {aot.load_seconds:.3f}s")
        spec = None
        spec_kwargs = _spec_dict(args)
        if spec_kwargs:
            from .spec import SpecConfig

            spec = SpecConfig(**spec_kwargs)
        fleet = _toy_fleet(dp=args.dp, layers=args.layers,
                           num_blocks=args.blocks,
                           max_queue=args.max_queue,
                           flight_dir=args.flight_dir, audit=audit,
                           unified=args.unified, fault_plan=fault_plan,
                           alert_rules=alert_rules, aot=aot,
                           max_tokens_per_step=args.max_tokens_per_step,
                           spec=spec, burst_steps=args.burst,
                           roles=getattr(args, "roles_list", None))
    supervisor = None
    if args.max_restarts > 0:
        # self-healing by default (ISSUE 12): dead replicas restart
        # under capped exponential backoff, audit-degraded replicas are
        # quarantined and replaced, wedged steps are watchdogged.
        # --max-restarts 0 opts out (legacy exclude-forever semantics).
        from .resilience import FleetSupervisor, SupervisorConfig

        supervisor = FleetSupervisor(fleet, config=SupervisorConfig(
            max_restarts=args.max_restarts,
            watchdog_timeout_s=args.watchdog_timeout))
    server = CompletionServer(fleet, ServerConfig(
        host=args.host, port=args.port,
        max_queue=args.max_queue,
        default_timeout_s=args.timeout))
    pusher = None
    if args.push_gateway:
        from ..observability.push import PushGateway

        pusher = PushGateway(args.push_gateway, registry=fleet.registry,
                             interval_s=args.push_interval).start()
    await server.start()
    if supervisor is not None:
        supervisor.start()  # closed by fleet.stop() during shutdown
    loop = asyncio.get_running_loop()
    try:
        import signal

        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, server.request_shutdown)
    except (NotImplementedError, RuntimeError):
        pass  # swallow-ok: platform without signal-handler support (Windows/non-main loop); Ctrl-C still raises KeyboardInterrupt
    print(f"serving on http://{server.cfg.host}:{server.port} "
          f"dp={fleet.dp} mp={server.engine.mp} "
          "(POST /v1/completions; GET /healthz /readyz /metrics "
          "/v1/requests /v1/debug/compiles /v1/debug/profile "
          "/v1/debug/audit /v1/debug/alerts /v1/debug/history "
          "/v1/debug/wire)")
    try:
        await server.serve_forever()
    finally:
        if pusher is not None:
            pusher.close()
        if pf is not None:
            pf.shared.close_all()  # reap the worker processes
    return 0


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving.server",
        description="HTTP/SSE serving frontend (toy model demo + selftest)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--blocks", type=int, default=256)
    p.add_argument("--max-queue", type=int, default=64)
    p.add_argument("--timeout", type=float, default=None,
                   help="default per-request deadline (seconds)")
    p.add_argument("--mp", type=int, default=1,
                   help="tensor-parallel degree: init a mesh with this "
                        "mp axis before building the engines (needs that "
                        "many devices; on CPU set XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel fleet degree: N engine replicas "
                        "behind the prefix-affinity router (composes "
                        "with --mp: '--dp 2 --mp 2' is a dp×mp fleet of "
                        "2 replicas, each mesh-spanning 2 shards)")
    p.add_argument("--push-gateway", default=None, metavar="URL",
                   help="POST Prometheus text exposition of the fleet "
                        "registry to this URL on an interval (daemon "
                        "thread, capped exponential backoff on failure)")
    p.add_argument("--push-interval", type=float, default=15.0,
                   help="push-gateway export interval in seconds")
    p.add_argument("--fault-plan", default=None, metavar="FILE",
                   help="JSON fault plan for deterministic chaos runs "
                        "(serving/faultinject.py): named injection "
                        "points scheduled by (replica, engine step) — "
                        "engine_step_raise, pool_exhaust, slow_step, "
                        "kernel_corrupt; each fires exactly once and is "
                        "recorded as lifecycle/flight events")
    p.add_argument("--max-restarts", type=int, default=5, metavar="K",
                   help="self-healing supervisor: restarts allowed per "
                        "replica inside the crash-loop window before "
                        "permanent exclusion (capped exponential "
                        "backoff between attempts; audit-degraded "
                        "replicas are quarantined and replaced).  0 "
                        "disables supervision — a dead replica stays "
                        "excluded until an operator acts")
    p.add_argument("--watchdog-timeout", type=float, default=60.0,
                   metavar="S",
                   help="per-replica step watchdog: a step exceeding "
                        "this marks the replica unhealthy (excluded "
                        "from routing) and escalates to a restart if "
                        "the stall persists; only with supervision on")
    p.add_argument("--alert-rules", default=None, metavar="FILE",
                   help="JSON alert rule set evaluated over the metrics "
                        "history (observability/alerts.py): threshold / "
                        "rate / SLO burn-rate rules with step-indexed "
                        "windows; omitted = the default serving rule "
                        "set (pool exhaustion, goodput burn, compile "
                        "storms, restart/quarantine churn, ...)")
    p.add_argument("--flight-dir", default=None, metavar="DIR",
                   help="write flight-recorder post-mortem bundles "
                        "(engine death, preemption storms, 429 bursts, "
                        "drain overruns, numerics divergences) into "
                        "this directory")
    p.add_argument("--audit-sample", type=int, default=None, metavar="N",
                   help="enable online numerics auditing with a shadow-"
                        "oracle re-execution every Nth engine step "
                        "(NaN/Inf sentinel + logit telemetry on every "
                        "step; .npz repros land in --flight-dir); off "
                        "by default")
    p.add_argument("--max-tokens-per-step", type=int, default=None,
                   metavar="T",
                   help="unified ragged packing: per-step token budget "
                        "shared by decode rows, prefill chunks and "
                        "(with --spec-decode) draft verification; "
                        "required by --spec-decode")
    p.add_argument("--spec-decode", action="store_true",
                   help="speculative decoding (ISSUE 18): a host-side "
                        "n-gram proposer drafts tokens per decode-"
                        "resident request and the engine verifies them "
                        "as short chunks packed into the unified ragged "
                        "step — greedy outputs are token-identical with "
                        "strictly fewer engine steps.  Requires "
                        "--unified and --max-tokens-per-step; composes "
                        "with --workers (the spec config rides the wire "
                        "handshake as deployment identity)")
    p.add_argument("--burst", type=int, default=0, metavar="N",
                   help="device-resident decode bursts (ISSUE 19): when "
                        "the running set is a decode-only resident "
                        "cohort, ONE compiled program runs up to N "
                        "decode steps on-device (in-trace KV append + "
                        "sampling + EOS masking) and ships the [B, N] "
                        "token buffer back in one host round-trip; "
                        "token streams are bit-identical to per-step "
                        "decode.  0 disables; mutually inert with "
                        "--spec-decode (spec drafting wins).  Composes "
                        "with --workers (forwarded through the worker "
                        "spec) and --aot-save (the burst bucket lattice "
                        "is enumerated into the artifact)")
    p.add_argument("--spec-k", type=int, default=4, metavar="K",
                   help="--spec-decode: max draft tokens proposed per "
                        "request per step (default 4)")
    p.add_argument("--unified", action="store_true",
                   help="serve through the unified ragged step program "
                        "(one packed prefill+decode launch per engine "
                        "step, collapsed bucket set; at mp>1 the Pallas "
                        "fast path runs mesh-spanning via shard_map)")
    p.add_argument("--aot-save", default=None, metavar="DIR",
                   help="enumerate + jax.export the full bucketed "
                        "program set of the configured engine "
                        "(--layers/--blocks/--unified/--mp) into an AOT "
                        "artifact directory (manifest + StableHLO), "
                        "then exit — the compile-once build step of "
                        "ISSUE 15")
    p.add_argument("--aot-path", default=None, metavar="DIR",
                   help="serve from a saved AOT artifact: every replica "
                        "(and every supervisor rebuild) shares one "
                        "loaded program set and the engines trace "
                        "NOTHING (manifest mismatches fail loudly at "
                        "boot; composes with --selftest, which then "
                        "asserts zero traces)")
    p.add_argument("--aot-max-seq", type=int, default=128, metavar="T",
                   help="--aot-save: bound the saved bucket universe to "
                        "sequences of at most T tokens (default 128; "
                        "the pool capacity caps it either way — a "
                        "serving step past the bound fails loudly "
                        "instead of retracing)")
    p.add_argument("--workers", type=int, default=0, metavar="N",
                   help="cross-process fleet (ISSUE 16): N worker "
                        "PROCESSES (python -m paddle_tpu.serving.worker)"
                        " behind the same prefix-affinity router and "
                        "self-healing supervisor, speaking the length-"
                        "prefixed JSON wire protocol over localhost — "
                        "kill -9 a worker and the fleet reroutes, "
                        "respawns it off the shared --aot-path artifact "
                        "and loses nothing.  0 = in-process replicas "
                        "(--dp)")
    p.add_argument("--roles", default=None, metavar="SPEC",
                   help="prefill/decode disaggregation (ISSUE 20): "
                        "per-replica role counts, e.g. "
                        "'prefill:1,decode:2'.  Counts must sum to the "
                        "fleet size (--dp or --workers).  Admissions "
                        "route to prefill specialists; each request "
                        "migrates (with its computed prompt KV) to a "
                        "decode specialist at its first-token boundary")
    p.add_argument("--autoscale", action="store_true",
                   help="with --workers: enable the SLO-driven "
                        "autoscaler (alert firings → bounded worker "
                        "scale actions).  Bounds via --autoscale-min / "
                        "--autoscale-max")
    p.add_argument("--autoscale-min", type=int, default=1, metavar="N",
                   help="autoscaler floor: never drain below N live "
                        "workers (default 1)")
    p.add_argument("--autoscale-max", type=int, default=0, metavar="N",
                   help="autoscaler ceiling: never provision above N "
                        "workers (0 = the fleet's --workers count; the "
                        "index space is fixed at boot)")
    p.add_argument("--rebalance", action="store_true",
                   help="with --workers: enable the prefix-cache "
                        "rebalancer (hot-prefix replication across "
                        "replicas)")
    p.add_argument("--aot-warm", action="store_true",
                   help="with --aot-save: execute every exported "
                        "program once right after saving (device-warms "
                        "the artifact and fills the compilation cache); with "
                        "--workers: each worker warm-executes the "
                        "loaded artifact at boot so the FIRST request "
                        "wave pays zero lazy compiles (wall seconds "
                        "recorded as serving_aot_warm_seconds)")
    p.add_argument("--selftest", action="store_true",
                   help="boot on an ephemeral port, serve one completion "
                        "against the toy fleet through the router path, "
                        "exit 0 on success")
    args = p.parse_args(argv)
    from ..utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.dp < 1:
        p.error(f"--dp must be >= 1, got {args.dp}")
    if args.workers < 0:
        p.error(f"--workers must be >= 0, got {args.workers}")
    if args.workers:
        if args.dp > 1:
            p.error("--workers and --dp are the two fleet modes — pick "
                    "one (cross-process: --workers N; in-process: "
                    "--dp N)")
        if args.autoscale_min < 1:
            p.error(f"--autoscale-min must be >= 1, got "
                    f"{args.autoscale_min}")
        if args.autoscale_max < 0:
            p.error(f"--autoscale-max must be >= 0, got "
                    f"{args.autoscale_max}")
        if args.autoscale_max and args.autoscale_max < args.autoscale_min:
            p.error("--autoscale-max must be >= --autoscale-min")
    elif args.autoscale or args.rebalance:
        p.error("--autoscale/--rebalance act on the cross-process "
                "worker pool; they require --workers N")
    args.roles_list = None
    if args.roles:
        from .fleet import parse_roles

        try:
            args.roles_list = parse_roles(args.roles)
        except ValueError as e:
            p.error(f"--roles: {e}")
        size = args.workers if args.workers else args.dp
        if len(args.roles_list) != size:
            p.error(f"--roles names {len(args.roles_list)} replica(s) "
                    f"but the fleet has {size} (--workers/--dp)")
    if args.audit_sample is not None and args.audit_sample < 1:
        p.error(f"--audit-sample must be >= 1, got {args.audit_sample}")
    if args.max_restarts < 0:
        p.error(f"--max-restarts must be >= 0, got {args.max_restarts}")
    if args.spec_decode:
        if not args.unified:
            p.error("--spec-decode verifies drafts inside the unified "
                    "ragged step program; it requires --unified")
        if args.max_tokens_per_step is None:
            p.error("--spec-decode needs --max-tokens-per-step: drafts "
                    "compete for the step's leftover token budget")
        if args.spec_k < 0:
            p.error(f"--spec-k must be >= 0, got {args.spec_k}")
    if args.burst < 0:
        p.error(f"--burst must be >= 0, got {args.burst}")
    if args.mp > 1 and not args.workers:
        # tensor-parallel serving (ISSUE 5): build the mesh BEFORE any
        # engine (selftest included — the probe must exercise the real
        # degree) so parameters and KV pools land sharded.  On CPU this
        # needs XLA_FLAGS=--xla_force_host_platform_device_count=N.
        # With --workers the mesh lives in each WORKER process (ISSUE
        # 18): the router forwards mp through the worker spec and never
        # builds a mesh of its own.
        from ..distributed import topology

        topology.init_mesh(mp=args.mp)
    if args.aot_save:
        if args.aot_max_seq < 1:
            p.error(f"--aot-max-seq must be >= 1, got {args.aot_max_seq}")
        from .aot import AotArtifact

        eng = _toy_engine(layers=args.layers, num_blocks=args.blocks,
                          unified=args.unified, burst_steps=args.burst)
        art = AotArtifact.save(eng, args.aot_save,
                               max_seq_len=args.aot_max_seq)
        print("aot-save: " + json.dumps(art.describe(), indent=1))
        if args.aot_warm:
            # pre-compile every exported program at SAVE time (ISSUE 16
            # satellite): this fills the persistent compilation cache
            # (utils.compile_cache) so every later worker boot on this
            # machine compiles nothing
            wall = art.warm()
            print(f"aot-warm: executed {art.program_count} program(s) "
                  f"in {wall:.3f}s")
        return 0
    if args.selftest:
        if args.workers:
            # ISSUE 17 satellite: the selftest now covers the cross-
            # process fleet too — boots N workers, serves one completion
            # over HTTP, and (with --autoscale) asserts the autoscaler
            # actuator thread is live
            return asyncio.run(_selftest_procfleet_async(args))
        return asyncio.run(_selftest_async(
            dp=args.dp, audit_sample=args.audit_sample or 1,
            unified=args.unified, aot_path=args.aot_path,
            layers=args.layers, blocks=args.blocks))
    return asyncio.run(_serve_cli(args))


if __name__ == "__main__":
    import sys

    sys.exit(main())
