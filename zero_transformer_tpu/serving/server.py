"""Streaming HTTP front end for the continuous-batching engine.

Stdlib-only (``http.server`` threads + SSE) so the serving surface works in
this image without extra dependencies — the reference's only UI was a
CUDA+gradio app (reference ``app.py``). Endpoints:

- ``POST /generate``: JSON body ``{"prompt": str | "tokens": [int],
  "max_new_tokens": int, "seed": int, "timeout": float, "stream": bool}``.
  With ``stream`` (default true) the response is ``text/event-stream``: one
  ``data: {"token": id, "text": piece}`` event per token — ``"text"`` is
  the empty string while the detokenizer buffers a piece mid-UTF-8, so
  every token id is on the wire (the fleet router's mid-stream resume
  point) and joining ``e["text"]`` still reconstructs the full text — and
  a final ``data: {"done": true, "status": ..., "text": full}``. Without, a
  single JSON document. Backpressure maps to HTTP 429 (queue full) / 400
  (invalid request).
- ``GET /healthz``: the engine's LIFECYCLE, with real status codes — 200
  only when READY; 503 while starting, degraded (breaker open), draining,
  or stopped, so a load balancer routes around a sick replica. Body:
  ``{"state", "uptime_s", "reloads", "breaker_open", ...}``.
- ``GET /metrics``: content-negotiated. The default stays the JSON snapshot
  (TTFT/ITL percentiles — with a pure-decode ``itl_decode_ms_*`` split
  isolating chunked-prefill interference — tokens/s, rejects, prefix-cache
  hit/miss/entry counters, resilience counters); an ``Accept`` header
  naming ``text/plain`` or ``openmetrics``
  (what a Prometheus scraper sends), or ``?format=prometheus``, gets the
  text exposition format backed by the engine's fixed-bucket histograms —
  O(buckets) per scrape, never the tick lock (docs/OBSERVABILITY.md).
- ``POST /admin/reload``: hot weight reload — load a standby msgpack tree
  off the tick thread, validate, swap between ticks without dropping a
  slot (also wired to SIGHUP by ``install_signal_handlers``).
- ``POST /admin/profile``: ``{"ticks": N}`` captures a ``jax.profiler``
  trace of the next N scheduler ticks into the engine's obs directory
  (same loopback/bearer-token gate as reload; 409 while DRAINING or when a
  capture is already running).

Request correlation: every request carries an id — inbound ``X-Request-Id``
(or body ``request_id``) when the caller supplies one for cross-service
correlation, generated at admission otherwise — echoed as an
``X-Request-Id`` response header on every /generate response (SSE and JSON,
success and rejection) and as ``request_id`` in the final SSE event. The
same id keys the request's span tree in the engine's tracer.

One scheduler thread drives ``engine.step()``; HTTP handler threads only
``submit()`` and drain per-request queues, so a slow client never stalls
decode for everyone else (the whole point of continuous batching).
Retryable rejections (drain, shed, breaker) map to 503 + ``Retry-After``;
request bodies are bounded (413) so an oversized POST can't balloon the
stdlib handler. SIGTERM (``install_signal_handlers``) begins a graceful
drain: admission closes, in-flight streams finish up to the drain
deadline, then the process exits 0.
"""
from __future__ import annotations

import http.client
import json
import math
import queue as queue_mod
import select
import signal
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional
from urllib.parse import urlsplit

from zero_transformer_tpu.serving.detok import StreamDecoder, decode_tokens
from zero_transformer_tpu.serving.engine import (
    FAILED,
    MIGRATED,
    REJECTED,
    RequestHandle,
    ServingEngine,
)
from zero_transformer_tpu.serving.resilience import READY, STOPPED, ReloadError
from zero_transformer_tpu.serving.slots import (
    page_span_from_wire,
    page_span_to_wire,
)

# how long an SSE handler blocks on the next token before re-checking that
# the client is still connected (a request parked in the admission queue, or
# a half-open peer that will never RST, produces no write to fail on)
_LIVENESS_POLL_S = 0.5


def _client_gone(conn) -> bool:
    """True when the peer has closed its end: for SSE the client sends
    nothing after the POST body, so a READABLE socket whose peek returns
    b'' is a FIN. Half-open peers (host gone, no FIN/RST) still need the
    write-failure path — this catches the common orderly close."""
    try:
        readable, _, _ = select.select([conn], [], [], 0)
        if readable:
            return conn.recv(1, socket.MSG_PEEK) == b""
    except OSError:
        return True
    return False


class ServingServer:
    """Own the HTTP server + the engine's scheduler thread."""

    def __init__(self, engine: ServingEngine, tokenizer, host: str = "127.0.0.1",
                 port: int = 8000, max_body_bytes: int = 1 << 20,
                 reload_source=None, admin_token: Optional[str] = None,
                 max_ingest_bytes: int = 256 << 20):
        self.engine = engine
        self.tokenizer = tokenizer
        self.max_body_bytes = max_body_bytes
        # /ingest bodies carry raw KV pages — bounded separately from the
        # JSON request bound (a real span is MBs where a prompt is KBs)
        self.max_ingest_bytes = max_ingest_bytes
        # imported streams awaiting their /attach (rid -> (handle,
        # ingested_at)); the attach POPS, so a stream is consumed exactly
        # once, and a TTL sweep cancels orphans (router died between the
        # ship ack and the attach) so they cannot burn decode capacity or
        # leak handles forever
        self._pending_streams: Dict[str, tuple] = {}
        self._streams_lock = threading.Lock()
        self.attach_ttl_s = 300.0
        # page shipper: the engine's tick thread enqueues (payload, target,
        # on_done); this thread serializes + POSTs to <target>/ingest so
        # the tick thread never blocks on a peer's socket
        self._ship_queue: "queue_mod.Queue" = queue_mod.Queue()
        self._ship_thread = threading.Thread(
            target=self._ship_loop, name="serve-shipper", daemon=True
        )
        if engine.page_shipper is None:
            engine.page_shipper = self._enqueue_ship
        # reload source for SIGHUP / POST /admin/reload: a msgpack path, or
        # a loader callable — called with the request's path when one is
        # given, with no args otherwise (serve.py's loader replays the full
        # startup path: import -> quantize -> TP shard)
        self.reload_source = reload_source
        # /admin/* access: loopback peers always; non-loopback only with
        # this bearer token (weight swapping must not be open to any peer
        # that can reach a --host 0.0.0.0 port)
        self.admin_token = admin_token
        self._stop = threading.Event()
        self._scheduler = threading.Thread(
            target=engine.run, args=(self._stop,), name="serve-scheduler",
            daemon=True,
        )
        outer = self

        class Handler(BaseHTTPRequestHandler):
            # quiet by default; the engine's metrics logger is the log surface
            def log_message(self, fmt, *args):  # noqa: A003
                pass

            def _json(self, code: int, obj, headers=None) -> None:
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for name, value in (headers or {}).items():
                    self.send_header(name, value)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                path, _, query = self.path.partition("?")
                if path == "/healthz":
                    self._json(*outer._healthz())
                elif path == "/admin/spans":
                    # the fleet-trace stitch seam (PR 15): the router pulls
                    # this replica's span tail for one request id and maps
                    # it onto its own clock — admin-gated like every other
                    # /admin route (span attrs can carry prompt-adjacent
                    # metadata)
                    if not outer._admin_allowed(self):
                        self._json(403, {"error": "admin endpoint: loopback "
                                                  "or bearer token required"})
                        return
                    self._json(*outer._admin_spans(query))
                elif path == "/metrics":
                    accept = self.headers.get("Accept") or ""
                    if (
                        "format=prometheus" in query
                        or "text/plain" in accept
                        or "openmetrics" in accept
                    ):
                        # the Prometheus scrape path: its Accept header
                        # names text/plain;version=0.0.4 (and/or
                        # openmetrics); JSON dashboards keep the default
                        body = outer.engine.prometheus_text().encode()
                        self.send_response(200)
                        self.send_header(
                            "Content-Type",
                            "text/plain; version=0.0.4; charset=utf-8",
                        )
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    else:
                        self._json(200, outer.engine.metrics_snapshot())
                else:
                    self._json(404, {"error": f"no route {self.path}"})

            def do_POST(self):  # noqa: N802
                if self.path not in (
                    "/generate", "/attach", "/ingest",
                    "/admin/reload", "/admin/profile",
                    "/admin/migrate", "/admin/migrate_all",
                    "/admin/brownout",
                ):
                    self._json(404, {"error": f"no route {self.path}"})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                except ValueError:
                    self._json(400, {"error": "bad Content-Length"})
                    return
                if self.path == "/ingest":
                    # binary page-span body — its own (much larger) bound,
                    # and no JSON parse
                    if length < 0 or length > outer.max_ingest_bytes:
                        self.close_connection = True
                        self._json(413 if length > 0 else 400, {
                            "error": (
                                f"ingest body must be 0..{outer.max_ingest_bytes} bytes"
                            ),
                        })
                        return
                    outer._ingest(self, self.rfile.read(length))
                    return
                if length < 0:
                    # rfile.read(-1) would read until EOF — unbounded, the
                    # exact balloon the body bound exists to prevent
                    self._json(400, {"error": "bad Content-Length"})
                    return
                if length > outer.max_body_bytes:
                    # bound BEFORE reading: an oversized POST must not
                    # balloon the stdlib handler's memory. The unread body
                    # would desynchronize the connection — close it.
                    self.close_connection = True
                    self._json(413, {
                        "error": f"body exceeds {outer.max_body_bytes} bytes",
                    })
                    return
                try:
                    req = json.loads(self.rfile.read(length) or b"{}")
                except (ValueError, json.JSONDecodeError):
                    self._json(400, {"error": "malformed JSON body"})
                    return
                if not isinstance(req, dict):
                    # valid JSON but not an object ([1,2], "x") — still the
                    # client's error, not a handler-thread traceback
                    self._json(400, {"error": "body must be a JSON object"})
                    return
                if self.path.startswith("/admin/"):
                    if not outer._admin_allowed(self):
                        self._json(403, {"error": "admin endpoint: loopback "
                                                  "or bearer token required"})
                        return
                    if self.path == "/admin/reload":
                        self._json(*outer._reload(req))
                    elif self.path == "/admin/migrate":
                        self._json(*outer._migrate(req))
                    elif self.path == "/admin/migrate_all":
                        self._json(*outer._migrate_all(req))
                    elif self.path == "/admin/brownout":
                        self._json(*outer._brownout(req))
                    else:
                        self._json(*outer._profile(req))
                elif self.path == "/attach":
                    outer._attach(self, req)
                else:
                    outer._generate(self, req)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    # ------------------------------------------------------------ lifecycle

    def start(self, start_scheduler: bool = True) -> None:
        """``start_scheduler=False`` serves HTTP with the engine still
        STARTING (tests assert /healthz is 503 before readiness; a real
        deployment would use it to finish warmup before taking traffic) —
        call ``start_scheduler()`` to go READY."""
        if not self._ship_thread.ident:
            self._ship_thread.start()
        if start_scheduler:
            self.start_scheduler()
        self._server_thread = threading.Thread(
            target=self._httpd.serve_forever, name="serve-http", daemon=True
        )
        self._server_thread.start()

    def start_scheduler(self) -> None:
        if not self._ship_thread.ident:
            self._ship_thread.start()
        if not self._scheduler.ident:
            self._scheduler.start()

    def serve_forever(self) -> None:
        if not self._ship_thread.ident:
            self._ship_thread.start()
        self.start_scheduler()
        try:
            self._httpd.serve_forever()
        finally:
            self.stop()

    def stop(self) -> None:
        self._stop.set()
        self._httpd.shutdown()

    # ------------------------------------------------------------ resilience

    def _healthz(self):
        """(code, body) for /healthz: 200 ONLY when the engine is READY and
        its scheduler thread is alive — warming up, degraded, draining, and
        stopped all answer 503 so a load balancer stops routing here."""
        # orphan sweep rides the health poll (routers probe every replica
        # continuously), so a replica that stops receiving ingest/attach
        # traffic still cancels un-attached imports at the TTL
        self._sweep_pending_streams()
        state = self.engine.lifecycle.state
        alive = self._scheduler.is_alive() or not self._scheduler.ident
        if not alive and state != STOPPED:
            state = "scheduler dead"
        ok = state == READY and alive
        return (200 if ok else 503), {
            "status": "ok" if ok else state,
            "state": state,
            # this replica's monotonic clock AT ANSWER TIME: the router
            # brackets the probe with its own clock and estimates the
            # per-process offset (NTP-style midpoint) that lets it map
            # this replica's span timestamps onto one fleet timeline
            "clock_monotonic": self.engine.now(),
            "uptime_s": round(self.engine.lifecycle.uptime_s, 3),
            "reloads": self.engine.stats["reloads"],
            "breaker_open": self.engine._breaker.open,
            "slots": self.engine.n_slots,
            "active": self.engine.active_count,
            "prefilling": len(self.engine._prefilling),
            "queued": self.engine.queue_depth,
            # the fleet router's admission inputs (ISSUE 9): everything its
            # least-loaded policy needs rides the same cheap health poll —
            # one GET instead of a /metrics scrape per routing refresh
            "itl_ewma_ms": round(
                (self.engine._itl_ewma.value or 0.0) * 1e3, 4
            ),
            "queue_depth": self.engine.queue_depth,
            "active_slots": self.engine.active_count,
            "free_pages": self.engine.free_pages,
            # disaggregation inputs (ISSUE 12): the router's role-aware
            # placement reads both off the same cheap poll, and the
            # page-pool pressure stats ride along so the router can mirror
            # them as per-replica gauges without a /metrics scrape
            "role": self.engine.role,
            # a constant: the router ships pages only to a replica whose
            # probe says so (``Replica.importable``), never into the unknown
            "kv_layout": "paged",
            "draft_k": self.engine.draft_k,
            "migrations_in_flight": self.engine.migrations_in_flight,
            "page_faults": self.engine.stats["page_faults"],
            "cow_copies": self.engine.slots.cow_copies,
            # overload-isolation inputs (ISSUE 18): the fleet brownout
            # controller reads the rung it last pushed back off the same
            # poll (convergence check), and per-class queue depths let the
            # router see WHICH class is backed up, not just how much
            "brownout_rung": self.engine.brownout_rung,
            "queue_by_class": self.engine._queue.counts(),
        }

    def _admin_allowed(self, handler) -> bool:
        peer = handler.client_address[0]
        if peer in ("127.0.0.1", "::1", "::ffff:127.0.0.1"):
            return True
        if self.admin_token:
            auth = handler.headers.get("Authorization", "")
            return auth == f"Bearer {self.admin_token}"
        return False

    def _admin_spans(self, query: str):
        """(code, body) for GET /admin/spans?request_id=<rid>[&tail=N]:
        this replica's span tail for one request track (or the whole ring
        tail with no request_id), plus the engine clock reading the router
        needs to place these spans on the fleet timeline."""
        from urllib.parse import parse_qs

        params = parse_qs(query)
        rid = (params.get("request_id") or [None])[0]
        try:
            tail = int((params.get("tail") or [2000])[0])
        except (TypeError, ValueError):
            return 400, {"error": "tail must be an integer"}
        spans = self.engine.tracer.track_dicts(
            track=rid if rid else None, tail=max(1, min(tail, 20000)),
        )
        return 200, {
            "request_id": rid or "",
            "clock_monotonic": self.engine.now(),
            "role": self.engine.role,
            "spans": spans,
            "spans_dropped": self.engine.tracer.dropped,
        }

    def _reload(self, req: dict):
        """(code, body) for POST /admin/reload: load a standby tree in THIS
        handler thread (off the tick thread), validate, swap between ticks.
        409 on a corrupt/mismatched artifact — the engine stays READY on
        the old weights.

        A request path is handed to the CONFIGURED loader when one exists
        (so int8-quantized / TP-sharded servers prepare the reloaded tree
        exactly like the startup tree); the bare msgpack import is only the
        fallback for servers configured without a loader."""
        path = req.get("params")
        if callable(self.reload_source):
            loader = self.reload_source
            source = (lambda: loader(path)) if path else loader
        elif path or isinstance(self.reload_source, str):
            load_path = path or self.reload_source

            def source():
                from zero_transformer_tpu.checkpoint import import_params_msgpack

                return import_params_msgpack(load_path)
        else:
            return 400, {"error": "no reload source: pass {\"params\": <path>}"}
        try:
            info = self.engine.reload_params(source)
        except ReloadError as exc:
            return 409, {
                "error": str(exc),
                "state": self.engine.lifecycle.state,
                "reloads": self.engine.stats["reloads"],
            }
        # wait on THIS reload's swap event (not a shared latest-reload flag:
        # concurrent staging must not let one caller claim another's swap)
        swapped = info["swapped"].wait(timeout=30.0)
        return (200 if swapped else 202), {
            "reloaded": swapped,
            "reloads": self.engine.stats["reloads"],
            "state": self.engine.lifecycle.state,
        }

    def _profile(self, req: dict):
        """(code, body) for POST /admin/profile: stage a jax.profiler
        capture of the next N scheduler ticks, landing in the engine's obs
        directory next to the flight-recorder dumps. 202 (the capture runs
        asynchronously on the tick thread); 409 while draining, when a
        capture is already in progress, or without an obs directory."""
        try:
            ticks = int(req.get("ticks", 20))
        except (TypeError, ValueError):
            return 400, {"error": "ticks must be an integer"}
        try:
            info = self.engine.request_profile(ticks)
        except ValueError as exc:
            return 400, {"error": str(exc)}
        except RuntimeError as exc:
            return 409, {"error": str(exc), "state": self.engine.lifecycle.state}
        return 202, {"accepted": True, **info}

    def _brownout(self, req: dict):
        """(code, body) for POST /admin/brownout: set this replica's
        brownout rung (``{"rung": "no_spec"}``). The fleet router's
        controller drives this on every transition; operators can also hit
        it directly to force or clear a rung. Idempotent — re-posting the
        current rung is a 200 no-op."""
        rung = req.get("rung")
        if not isinstance(rung, str):
            return 400, {"error": "rung must be a string"}
        try:
            info = self.engine.set_brownout(rung)
        except ValueError as exc:
            return 400, {"error": str(exc)}
        return 200, info

    # -------------------------------------------- disaggregation / migration

    def _enqueue_ship(self, payload: dict, target: str, on_done) -> None:
        """The engine's ``page_shipper`` seam: hand the export to the
        shipper thread and return immediately — the tick thread never
        blocks on a peer replica's socket."""
        self._ship_queue.put((payload, target, on_done))

    def _ship_loop(self) -> None:
        while not self._stop.is_set():
            try:
                item = self._ship_queue.get(timeout=0.2)
            except queue_mod.Empty:
                continue
            payload, target, on_done = item
            try:
                err = self._ship_once(payload, target)
            except Exception as exc:  # noqa: BLE001 — a shipper crash must fail ONE migration, not the thread
                err = f"{type(exc).__name__}: {exc}"
            on_done(err)

    def _ship_once(self, payload: dict, target: str) -> Optional[str]:
        """POST one page-span payload to ``<target>/ingest``. Returns None
        on an accepted ingest, else a reason string (the engine fails that
        migration retryably and the router falls back to recompute)."""
        blob = page_span_to_wire(payload)
        parts = urlsplit(target if "//" in target else f"http://{target}")
        host = parts.hostname or "127.0.0.1"
        port = parts.port or 80
        conn = http.client.HTTPConnection(host, port, timeout=30.0)
        try:
            conn.request(
                "POST", "/ingest", blob,
                {"Content-Type": "application/octet-stream",
                 "X-Request-Id": str(payload.get("request_id", ""))},
            )
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                try:
                    doc = json.loads(body or b"{}")
                except ValueError:
                    doc = {}
                return (
                    f"ingest at {target} returned {resp.status}: "
                    f"{doc.get('error', '')}"
                )
            return None
        except (OSError, http.client.HTTPException) as exc:
            return f"ship to {target} failed: {type(exc).__name__}: {exc}"
        finally:
            conn.close()

    def _ingest(self, handler, blob: bytes) -> None:
        """POST /ingest: accept a migrated stream's pages + carry. The
        imported handle parks in the pending-streams table until the
        router ATTACHES (tokens that decode meanwhile buffer in the
        handle's queue — nothing is lost, TTFT overlaps the attach)."""
        try:
            payload = page_span_from_wire(blob)
        except ValueError as exc:
            handler._json(400, {"error": f"bad page-span body: {exc}"})
            return
        handle = self.engine.import_stream(payload)
        if handle.status in (REJECTED, FAILED):
            code = 503 if handle.retryable else 409
            handler._json(code, {
                "error": handle.error, "status": handle.status,
                "request_id": handle.rid,
            }, headers={"X-Request-Id": handle.rid})
            return
        self._sweep_pending_streams()
        with self._streams_lock:
            displaced = self._pending_streams.pop(handle.rid, None)
            self._pending_streams[handle.rid] = (handle, time.monotonic())
        if displaced is not None:
            # duplicate rid (a re-shipped stream whose earlier ingest ack
            # was lost): the NEW import is the live one — cancel the
            # displaced handle so it cannot decode its budget unwatched
            displaced[0].cancel()
        handler._json(200, {
            "accepted": True, "request_id": handle.rid,
        }, headers={"X-Request-Id": handle.rid})

    def _sweep_pending_streams(self) -> None:
        """Cancel + drop imported streams nobody attached within the TTL:
        an orphan (its router died between ship ack and attach) must not
        decode its whole budget into the void or leak its handle."""
        cutoff = time.monotonic() - self.attach_ttl_s
        with self._streams_lock:
            stale = [
                rid for rid, (_, t0) in self._pending_streams.items()
                if t0 < cutoff
            ]
            dropped = [self._pending_streams.pop(rid) for rid in stale]
        for handle, _ in dropped:
            handle.cancel()

    def _attach(self, handler, req: dict) -> None:
        """POST /attach {"request_id"}: take over an imported stream's SSE.
        Pops the pending entry — a stream attaches exactly once; an unknown
        id is a clean 404 (the router then falls back to recompute)."""
        self._sweep_pending_streams()
        rid = str(req.get("request_id", ""))
        with self._streams_lock:
            handle, _ = self._pending_streams.pop(rid, (None, 0.0))
        if handle is None:
            handler._json(404, {
                "error": f"no pending stream {rid!r}", "request_id": rid,
            }, headers={"X-Request-Id": rid})
            return
        try:
            handler.send_response(200)
            handler.send_header("Content-Type", "text/event-stream")
            handler.send_header("Cache-Control", "no-cache")
            handler.send_header("X-Request-Id", handle.rid)
            handler.end_headers()
        except (BrokenPipeError, ConnectionResetError, OSError):
            # the attacher vanished between POST and headers: the entry is
            # already popped (attach is consume-once), so cancel — the
            # stream must not decode its budget into the void; the
            # router's retry gets a 404 and the recompute fallback covers
            handle.cancel()
            return
        self._stream_events(handler, handle)

    def _migrate(self, req: dict):
        """(code, body) for POST /admin/migrate {"request_id", "target"}:
        tag one live stream for migration. The export happens between
        ticks; the stream's open SSE ends with a ``migrated`` done event
        naming the target, which the router turns into an attach hop."""
        rid = str(req.get("request_id", ""))
        target = str(req.get("target", ""))
        if not rid or not target:
            return 400, {"error": "request_id and target are required"}
        if self.engine.request_migration(rid, target):
            return 202, {"requested": True, "request_id": rid,
                         "target": target}
        return 404, {"error": f"no live stream {rid!r}", "request_id": rid}

    def _migrate_all(self, req: dict):
        """(code, body) for POST /admin/migrate_all {"target"}: migrate
        every live stream (drain-as-migrate: rolling reload and scale-down
        use this instead of waiting out in-flight generations)."""
        target = str(req.get("target", ""))
        if not target:
            return 400, {"error": "target is required"}
        n = self.engine.request_migrate_all(target)
        return 202, {"requested": n, "target": target}

    def drain(self, deadline_s: Optional[float] = 30.0) -> None:
        """Begin a graceful drain and, once the engine reports STOPPED (or
        the deadline plus grace expires), shut the HTTP server down.
        ``deadline_s=None`` honors the engine contract — wait indefinitely
        for in-flight generations (no silent 10-second cutoff)."""
        self.engine.begin_drain(deadline_s)
        give_up = (
            None if deadline_s is None
            else time.monotonic() + deadline_s + 10.0
        )
        while self.engine.lifecycle.state != STOPPED and (
            give_up is None or time.monotonic() < give_up
        ):
            time.sleep(0.05)
        self.stop()

    def install_signal_handlers(
        self, drain_deadline_s: Optional[float] = 30.0
    ) -> None:
        """SIGTERM -> graceful drain (in a helper thread: the handler must
        return immediately); SIGHUP -> hot reload from ``reload_source``.
        The drain ends with ``stop()``, which returns the blocking
        ``serve_forever()`` caller — the process then exits 0, the contract
        an orchestrator's preemption hook expects."""

        def on_term(signum, frame):
            threading.Thread(
                target=self.drain, args=(drain_deadline_s,),
                name="serve-drain", daemon=True,
            ).start()

        def on_hup(signum, frame):
            if self.reload_source is None:
                return

            def _reload():
                try:
                    self._reload({})
                except Exception:
                    pass  # already counted/evented by the engine

            threading.Thread(target=_reload, name="serve-reload", daemon=True).start()

        signal.signal(signal.SIGTERM, on_term)
        if hasattr(signal, "SIGHUP"):
            signal.signal(signal.SIGHUP, on_hup)

    # -------------------------------------------------------------- request

    def _submit(self, req: dict, request_id: Optional[str] = None,
                trace_hop: Optional[int] = None,
                tenant: Optional[str] = None, qos: Optional[str] = None):
        if "tokens" in req:
            ids = [int(t) for t in req["tokens"]]
        else:
            ids = self.tokenizer.encode(str(req.get("prompt", "")).strip())
        return self.engine.submit(
            ids,
            max_new_tokens=int(req.get("max_new_tokens", 32)),
            seed=int(req.get("seed", 0)),
            timeout=float(req["timeout"]) if "timeout" in req else None,
            request_id=request_id,
            prefill_to=(
                str(req["prefill_to"]) if req.get("prefill_to") else None
            ),
            trace_hop=trace_hop,
            tenant=str(tenant or req.get("tenant") or "anon"),
            qos=qos if qos is not None else req.get("qos"),
        )

    @staticmethod
    def _trace_hop_of(handler) -> Optional[int]:
        """The router's propagated hop index (X-Trace-Hop), or None for a
        direct client — a garbled header is a dropped trace attr, never a
        rejected request."""
        raw = handler.headers.get("X-Trace-Hop")
        if raw is None:
            return None
        try:
            return int(raw)
        except (TypeError, ValueError):
            return None

    def _generate(self, handler, req: dict) -> None:
        # inbound correlation id (header wins over body field); the engine
        # generates one at admission when the client sent none — either way
        # every response carries it back as X-Request-Id
        rid_in = handler.headers.get("X-Request-Id") or req.get("request_id")
        try:
            handle = self._submit(
                req, request_id=rid_in,
                trace_hop=self._trace_hop_of(handler),
                # header wins over body field, same precedence as the
                # request id — the router forwards both in the relay body
                tenant=handler.headers.get("X-Tenant-Key"),
                qos=handler.headers.get("X-QoS-Class"),
            )
        except (TypeError, ValueError) as exc:
            # ill-typed field VALUES ({"timeout": "abc"}) are the client's
            # error — 400, not a dropped connection with a server traceback
            handler._json(400, {"error": f"bad request field: {exc}"})
            return
        rid_hdr = {"X-Request-Id": handle.rid}
        if handle.status == REJECTED:
            if handle.retryable:
                # drain / shed / backpressure: honest fast failure the
                # client should retry elsewhere — Retry-After sized by the
                # engine (remaining drain window, or a beat for the queue).
                # Quota exhaustion and brownout suspension are 429s too:
                # the CLIENT is over its allotment, the replica is fine
                err = handle.error or ""
                code = 429 if (
                    "queue full" in err or "quota" in err
                    or "brownout" in err
                ) else 503
                handler._json(
                    code,
                    {"error": handle.error, "status": handle.status,
                     "request_id": handle.rid},
                    headers={
                        "Retry-After": str(
                            max(1, math.ceil(handle.retry_after or 1.0))
                        ),
                        **rid_hdr,
                    },
                )
            else:
                handler._json(400, {"error": handle.error,
                                    "status": handle.status,
                                    "request_id": handle.rid},
                              headers=rid_hdr)
            return
        if handle.status == FAILED:
            # dead engine: an outage must read as 503, never as a 200 with
            # zero tokens
            handler._json(503, {"error": handle.error, "status": handle.status,
                                "request_id": handle.rid}, headers=rid_hdr)
            return
        if not req.get("stream", True):
            tokens = handle.result()
            if handle.status == FAILED:
                # the engine died AFTER admission — same outage as the
                # submit-time check above, same 503 (never a 200 with an
                # empty/truncated body a load balancer reads as healthy)
                handler._json(503, {"error": handle.error,
                                    "status": handle.status,
                                    "request_id": handle.rid}, headers=rid_hdr)
                return
            text = self._full_text(tokens)
            doc = {
                "status": handle.status, "tokens": tokens, "text": text,
                "request_id": handle.rid,
                # per-request cost ledger (PR 15): what this generation
                # actually consumed — the router completes it with
                # fleet-side fields and rolls it up per tenant
                "ledger": handle.ledger_snapshot(),
            }
            if handle.status == MIGRATED:
                # disaggregated handoff: the stream continues at this
                # replica — the router's attach hop picks it up there
                doc["migrated_to"] = handle.migrated_to
            handler._json(200, doc, headers=rid_hdr)
            return
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-cache")
        handler.send_header("X-Request-Id", handle.rid)
        handler.end_headers()
        self._stream_events(handler, handle)

    def _stream_events(self, handler, handle) -> None:
        """Pump one handle's token events onto an SSE connection whose
        headers are already sent (shared by /generate streams and /attach
        takeovers of imported streams)."""
        decoder = StreamDecoder(self.tokenizer)
        pieces: list = []
        eos = self.engine.eos_token_id
        # a live SSE consumer is draining the event queue from here on:
        # arm the per-handle emit-buffer bound so a consumer that stops
        # reading (stalled client) retires the stream instead of growing
        # the queue without limit
        handle.consumer_attached = True
        chaos = self.engine._chaos
        events_out = 0
        try:
            # the EOS token is swallowed, not break-ed on: the loop must end
            # on the 'done' event so handle.status is terminal by the time
            # the final SSE event reports it (the engine emits the eos token
            # BEFORE finishing the handle — an early break races that)
            while True:
                event = handle.next_event(timeout=_LIVENESS_POLL_S)
                if event is None:
                    # no token yet (queued, or a slow tick): is the client
                    # still there? A disconnected client must not hold a
                    # queue position — or later a slot — for a generation
                    # nobody will read
                    if _client_gone(handler.connection):
                        handle.cancel()
                        return
                    continue
                kind, token = event
                if kind != "token":
                    break
                events_out += 1
                if chaos is not None:
                    # slow_client fault: THIS consumer stops draining for
                    # ``duration`` seconds mid-stream — the engine keeps
                    # decoding into the bounded emit buffer meanwhile
                    stall = chaos.client_stall_s(events_out)
                    if stall > 0:
                        time.sleep(stall)
                if eos is not None and token == eos:
                    continue
                piece = decoder.push(token)
                if piece is not None:
                    pieces.append(piece)
                    self._event(handler, {"token": token, "text": piece})
                else:
                    # detok buffered the piece (partial UTF-8 across BPE
                    # boundaries): the token id still goes on the wire —
                    # the fleet router's mid-stream failover resumes from
                    # the ids it relayed, and a resume prompt missing
                    # buffered tokens would diverge even under greedy.
                    # text stays PRESENT (empty) so ``e["text"]`` consumers
                    # keep working and joins are unchanged
                    self._event(handler, {"token": token, "text": ""})
            tail = decoder.flush()
            if tail is not None:
                pieces.append(tail)
                self._event(handler, {"text": tail})
            done = {
                "done": True,
                "status": handle.status,
                "text": "".join(pieces),
                "error": handle.error,
                # the fleet router keys failover on this: a retryable
                # failure mid-stream is resumed on another replica
                "retryable": handle.retryable,
                "request_id": handle.rid,
                # per-request cost ledger (PR 15), cumulative across
                # migration hops (it rides the page-span payload)
                "ledger": handle.ledger_snapshot(),
            }
            if handle.status == MIGRATED:
                # zero-recompute handoff: the router attaches at the named
                # replica and the client stream continues seamlessly
                done["migrated_to"] = handle.migrated_to
            self._event(handler, done)
        except (BrokenPipeError, ConnectionResetError):
            # client went away: release the slot instead of decoding into
            # the void
            handle.cancel()

    def _event(self, handler, obj) -> None:
        handler.wfile.write(b"data: " + json.dumps(obj).encode() + b"\n\n")
        handler.wfile.flush()

    def _full_text(self, tokens) -> str:
        eos = self.engine.eos_token_id
        return decode_tokens(self.tokenizer, [t for t in tokens if t != eos])


def run_server(
    engine: ServingEngine,
    tokenizer,
    host: str = "127.0.0.1",
    port: int = 8000,
    background: bool = False,
    reload_source=None,
    drain_deadline_s: Optional[float] = 30.0,
    max_body_bytes: int = 1 << 20,
    admin_token: Optional[str] = None,
) -> Optional[ServingServer]:
    """Start the serving front end. ``background=True`` returns the running
    server (tests); otherwise blocks until SIGTERM (graceful drain, exit 0)
    or interrupt, with SIGHUP hot-reloading from ``reload_source``."""
    server = ServingServer(
        engine, tokenizer, host=host, port=port,
        max_body_bytes=max_body_bytes, reload_source=reload_source,
        admin_token=admin_token,
    )
    if background:
        server.start()
        return server
    server.install_signal_handlers(drain_deadline_s=drain_deadline_s)
    print(
        f"serving on http://{host}:{server.port} "
        f"({engine.n_slots} slots, cache_len {engine.cache_len}) — "
        "POST /generate, GET /healthz, GET /metrics (JSON; Prometheus text "
        "via Accept: text/plain), POST /admin/reload, POST /admin/profile; "
        f"SIGTERM drains ({drain_deadline_s}s deadline), SIGHUP reloads",
        flush=True,
    )
    server.serve_forever()
    return None
