"""JSON-RPC server: HTTP POST + GET-URI + websocket on one port
(reference rpc/lib/server/handlers.go + http_server.go).

- POST /            JSON-RPC 2.0 body
- GET  /<method>?a=b   URI route (params from query string)
- GET  /websocket   RFC6455 upgrade; JSON-RPC frames; subscribe/
                    unsubscribe stream events to the client
- GET  /            route listing (handlers.go writes the same)

The websocket side is hand-rolled (accept-key handshake + masked
client frames) so one threaded server owns both transports, matching
the reference's single listener.

Fan-out-scale serving (ours; no reference equivalent):

- hot read responses are served as pre-encoded JSON bytes out of the
  height/generation cache (rpc/cache.py) — a cached hit skips the
  handler AND the re-encode, splicing the stored result bytes into the
  response frame by concatenation;
- every websocket event is rendered to wire bytes once (rpc/core.py
  render_event_frame) and fanned out through a bounded per-client send
  queue drained by a writer thread, so one slow client backs up only
  its own queue. The slow-client policy is explicit ([rpc]
  ws_slow_policy): "drop" sheds that client's events with a counter,
  "disconnect" hangs up so the client's reconnect logic takes over.
"""

from __future__ import annotations

import base64
import collections
import hashlib
import logging
import socket
import struct
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional
from urllib.parse import parse_qsl, urlparse

from ..libs import tracing
from ..libs.events import Query
from . import jsonrpc
from .cache import RPCCache
from .core import ROUTES, UNSAFE_ROUTES, RPCEnvironment, cache_plan
from .jsonrpc import RPCError

LOG = logging.getLogger("rpc.server")

WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"

# cap on POST bodies: the RPC port is public, and Content-Length is
# attacker-controlled (same spirit as the remote-signer MAX_FRAME).
# Websocket frames share the cap — the 64-bit extended length field is
# equally attacker-controlled and was previously unbounded.
MAX_BODY_BYTES = 1 << 20

WS_SLOW_POLICIES = ("drop", "disconnect")


def _result_frame(id_, result_raw: bytes) -> bytes:
    """Splice pre-encoded result bytes into a JSON-RPC response frame
    without re-encoding the result."""
    return (b'{"jsonrpc":"2.0","id":' + jsonrpc.dumps(id_)
            + b',"result":' + result_raw + b"}")


class RPCServer:
    def __init__(self, env: RPCEnvironment, host: str, port: int,
                 unsafe: bool = False, max_open_connections: int = 0,
                 cache: Optional[RPCCache] = None,
                 ws_send_queue: int = 256, ws_slow_policy: str = "drop",
                 metrics=None):
        self.env = env
        self.unsafe = unsafe
        self.routes = dict(ROUTES)
        if unsafe:
            self.routes.update(UNSAFE_ROUTES)
        self.cache = cache
        if ws_slow_policy not in WS_SLOW_POLICIES:
            raise ValueError(
                f"[rpc] ws_slow_policy must be one of {WS_SLOW_POLICIES}, "
                f"got {ws_slow_policy!r}")
        self.ws_send_queue = max(1, int(ws_send_queue))
        self.ws_slow_policy = ws_slow_policy
        self.metrics = metrics  # RPCMetrics or None
        handler = _make_handler(self)

        outer = self

        class _LimitedHTTPServer(ThreadingHTTPServer):
            """Connection-capped server (reference
            rpc/lib/server/http_server.go StartHTTPServer →
            netutil.LimitListener): beyond max_open_connections,
            new connections are closed immediately instead of
            accumulating unbounded handler threads."""

            def process_request(self, request, client_address):
                if (outer.max_open_connections > 0
                        and outer._open_conns_add() is False):
                    try:
                        request.close()
                    except OSError:
                        pass
                    return
                try:
                    super().process_request(request, client_address)
                except BaseException:
                    # thread failed to start (fd/thread exhaustion):
                    # process_request_thread never runs, so release the
                    # slot here or it leaks forever
                    if outer.max_open_connections > 0:
                        outer._open_conns_done()
                    raise

            def process_request_thread(self, request, client_address):
                try:
                    super().process_request_thread(request, client_address)
                finally:
                    if outer.max_open_connections > 0:
                        outer._open_conns_done()

        self.max_open_connections = max_open_connections
        self._open_conns = 0
        self._open_lock = threading.Lock()
        self._httpd = _LimitedHTTPServer((host, port), handler)
        self._httpd.daemon_threads = True
        self._thread: Optional[threading.Thread] = None
        # live websocket connections: ThreadingHTTPServer.shutdown()
        # only stops the accept loop — established websockets would keep
        # being served (answering pings!) by their daemon threads, so a
        # "stopped" node would look alive to subscribed clients and
        # their auto-reconnect would never fire
        self._ws_conns: set = set()
        self._ws_lock = threading.Lock()
        # fan-out accounting (rpc_ws_subscribers / rpc_ws_dropped_total)
        self._subs_count = 0
        self._dropped: Dict[str, int] = {p: 0 for p in WS_SLOW_POLICIES}
        self._events_enqueued = 0
        self._stats_lock = threading.Lock()
        # cache invalidation: one NewBlock subscription per server
        self._inval_sub = None
        self._inval_thread: Optional[threading.Thread] = None
        self._inval_stop = threading.Event()

    @property
    def listen_addr(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"{host}:{port}"

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="rpc-http", daemon=True
        )
        self._thread.start()
        if self.cache is not None and self.cache.enabled:
            self._start_invalidation()
        LOG.info("RPC server listening on %s", self.listen_addr)

    def stop(self) -> None:
        self._inval_stop.set()
        if self._inval_sub is not None:
            try:
                self.env.event_bus.unsubscribe_all(self._inval_subscriber)
            except Exception:  # noqa: BLE001 - bus may already be down
                pass
            self._inval_sub = None
        self._httpd.shutdown()
        self._httpd.server_close()
        with self._ws_lock:
            conns = list(self._ws_conns)
        for c in conns:
            c.close()

    # -- cache invalidation (one EventBus NewBlock subscription) -------

    def _start_invalidation(self) -> None:
        from ..types.event_bus import EVENT_NEW_BLOCK, query_for_event

        self._inval_subscriber = f"rpc-cache-{id(self):x}"
        self._inval_sub = self.env.event_bus.subscribe(
            self._inval_subscriber, query_for_event(EVENT_NEW_BLOCK), 16)
        self._inval_stop.clear()
        # bind the cache OBJECT, not the attribute: tests/bench swap
        # self.cache to None to measure the uncached path while blocks
        # keep landing, and the object must keep seeing every bump or
        # its generational entries would survive the bypass window
        cache = self.cache

        def _drain():
            while not self._inval_stop.is_set():
                sub = self._inval_sub
                if sub is None or sub.cancelled:
                    return
                msg = sub.get(timeout=0.5)
                if msg is not None:
                    cache.on_new_block()

        self._inval_thread = threading.Thread(
            target=_drain, name="rpc-cache-inval", daemon=True)
        self._inval_thread.start()

    # -- open-connection cap -------------------------------------------

    def _open_conns_add(self) -> bool:
        with self._open_lock:
            if self._open_conns >= self.max_open_connections:
                return False
            self._open_conns += 1
            return True

    def _open_conns_done(self) -> None:
        with self._open_lock:
            self._open_conns -= 1

    def _ws_register(self, conn) -> None:
        with self._ws_lock:
            self._ws_conns.add(conn)

    def _ws_unregister(self, conn) -> None:
        with self._ws_lock:
            self._ws_conns.discard(conn)

    # -- fan-out accounting --------------------------------------------

    def _note_subs(self, delta: int) -> None:
        with self._stats_lock:
            self._subs_count = max(0, self._subs_count + delta)
            n = self._subs_count
        if self.metrics is not None:
            self.metrics.ws_subscribers.set(n)

    def _note_dropped(self, policy: str, n: int = 1) -> None:
        """Drop accounting is PER FRAME: a batch overflowing a client's
        queue by k counts k, never 1 — rpc_ws_dropped_total stays
        truthful under block-scoped bursts."""
        with self._stats_lock:
            self._dropped[policy] = self._dropped.get(policy, 0) + n
        if self.metrics is not None:
            self.metrics.ws_dropped.with_labels(policy).inc(n)

    def _note_enqueued(self, n: int = 1) -> None:
        with self._stats_lock:
            self._events_enqueued += n

    def debug_status(self) -> dict:
        """The /debug/rpc bundle: cache pressure + websocket fan-out
        state — queue occupancy against capacity is the backpressure
        signal tooling watches (tools/monitor.py)."""
        from .core import events_rendered_count

        with self._ws_lock:
            conns = list(self._ws_conns)
        depths = [c.queue_depth() for c in conns]
        hwms = [c._q_hwm for c in conns]
        with self._stats_lock:
            out_ws = {
                "conns": len(conns),
                "subscribers": self._subs_count,
                "send_queue_capacity": self.ws_send_queue,
                "max_queue_depth": max(depths, default=0),
                # high-water mark since connect: catches a queue that
                # backed up and drained between scrapes
                "max_queue_hwm": max(hwms, default=0),
                "slow_policy": self.ws_slow_policy,
                "events_enqueued": self._events_enqueued,
                "events_dropped": dict(self._dropped),
            }
        out_ws["events_rendered"] = events_rendered_count()
        return {
            "ws": out_ws,
            "cache": (self.cache.stats() if self.cache is not None
                      else {"enabled": False}),
        }

    # -- dispatch ------------------------------------------------------

    def call(self, method: str, params: dict) -> dict:
        fn = self.routes.get(method)
        if fn is None:
            raise RPCError(jsonrpc.ERR_METHOD_NOT_FOUND,
                           f"method {method!r} not found")
        return fn(self.env, params)

    def call_bytes(self, method: str, params: dict) -> bytes:
        """One RPC call, returning the RESULT as serialized JSON bytes.
        Cache-eligible calls ([rpc] cache_bytes > 0) are served from —
        and fill — the response cache; a hit never runs the handler or
        the JSON encoder. Raises exactly like call()."""
        cache = self.cache
        if cache is None or not cache.enabled:
            return jsonrpc.dumps(self.call(method, params))
        plan = cache_plan(self.env, method, params)
        if plan is None:
            return jsonrpc.dumps(self.call(method, params))
        key, generational = plan
        raw = cache.get(method, key)
        if raw is not None:
            return raw
        gen0 = cache.generation  # observed BEFORE the handler runs
        raw = jsonrpc.dumps(self.call(method, params))
        cache.put(method, key, raw, generational=generational,
                  generation=gen0)
        return raw


def _make_handler(server: RPCServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # route to our logger
            LOG.debug("http %s", fmt % args)

        # ---- plain HTTP ---------------------------------------------

        def _send_body(self, body: bytes, status: int = 200) -> None:
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _send_json(self, obj, status: int = 200) -> None:
            self._send_body(jsonrpc.dumps(obj), status=status)

        def do_POST(self):
            # rpc.handle is one POST from its first body byte to its last
            # response byte; its children split it into read (body +
            # JSON decode), one rpc.call per run of one method in a
            # batch (never one per request) and write
            tracer = tracing.get_tracer()
            with tracer.span("rpc.handle", cat="rpc",
                             request=tracer.request("post")) as sp:
                self._post(sp)

        def _post(self, sp) -> None:
            try:
                length = int(self.headers.get("Content-Length", 0))
            except (TypeError, ValueError):
                length = -1
            if not 0 <= length <= MAX_BODY_BYTES:
                # unread body bytes would desync this keep-alive stream
                self.close_connection = True
                return self._send_json(
                    jsonrpc.error_response(
                        None, jsonrpc.ERR_INVALID_REQUEST,
                        f"request body exceeds {MAX_BODY_BYTES} bytes"),
                    status=413)
            with tracing.span("rpc.read", cat="rpc", bytes=length):
                raw = self.rfile.read(length)
                try:
                    req = jsonrpc.loads(raw)
                except RPCError as e:
                    req = e
            if isinstance(req, RPCError):
                return self._send_json(
                    jsonrpc.error_response(None, req.code, req.message))
            if isinstance(req, list):  # batch
                sp.set(n=len(req), bytes=length)
                body = b"[" + b",".join(self._handle_runs(req)) + b"]"
            else:
                sp.set(n=1, bytes=length)
                body = self._handle_runs([req])[0]
            with tracing.span("rpc.write", cat="rpc", bytes=len(body)):
                self._send_body(body)

        def _handle_runs(self, reqs: list) -> list:
            """_handle_one over a batch, one rpc.call span per run of
            consecutive requests naming the same method."""
            out = []
            i = 0
            while i < len(reqs):
                method = (reqs[i].get("method")
                          if isinstance(reqs[i], dict) else None)
                j = i + 1
                while (j < len(reqs) and isinstance(reqs[j], dict)
                       and reqs[j].get("method") == method):
                    j += 1
                with tracing.span("rpc.call", cat="rpc", method=str(method),
                                  n=j - i):
                    out.extend(self._handle_one(r) for r in reqs[i:j])
                i = j
            return out

        def _handle_one(self, req) -> bytes:
            if not isinstance(req, dict) or "method" not in req:
                return jsonrpc.dumps(jsonrpc.error_response(
                    None, jsonrpc.ERR_INVALID_REQUEST, "invalid request"))
            id_ = req.get("id")
            try:
                raw = server.call_bytes(req["method"],
                                        req.get("params") or {})
                return _result_frame(id_, raw)
            except RPCError as e:
                return jsonrpc.dumps(
                    jsonrpc.error_response(id_, e.code, e.message, e.data))
            except Exception as e:  # noqa: BLE001 - handler crash → 32603
                LOG.exception("rpc %s failed", req.get("method"))
                return jsonrpc.dumps(jsonrpc.error_response(
                    id_, jsonrpc.ERR_INTERNAL, str(e)))

        def do_GET(self):
            parsed = urlparse(self.path)
            path = parsed.path.strip("/")
            if path == "websocket":
                return self._upgrade_websocket()
            if not path:  # route listing (handlers.go writeListOfEndpoints)
                listing = "".join(
                    f"<a href=\"/{m}\">/{m}</a><br>"
                    for m in sorted(server.routes)
                )
                body = f"<html><body>{listing}</body></html>".encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/html")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            # latin-1 round-trips every percent-decoded byte 1:1 (like
            # Go's string-of-bytes), so binary payloads in quoted params
            # survive; utf-8 would fold invalid sequences into U+FFFD
            params = dict(parse_qsl(parsed.query, encoding="latin-1"))
            # quoted URI values are RAW strings (reference handlers.go);
            # keep the marker so byte-typed params skip base64/hex
            params = {
                k: (jsonrpc.QuotedStr(v[1:-1])
                    if len(v) >= 2 and v[0] == v[-1] == '"' else v)
                for k, v in params.items()
            }
            try:
                raw = server.call_bytes(path, params)
                self._send_body(_result_frame("", raw))
            except RPCError as e:
                self._send_json(
                    jsonrpc.error_response("", e.code, e.message, e.data))
            except Exception as e:  # noqa: BLE001
                LOG.exception("rpc %s failed", path)
                self._send_json(
                    jsonrpc.error_response("", jsonrpc.ERR_INTERNAL, str(e)))

        # ---- websocket (rpc/lib/server/handlers.go wsConnection) ----

        def _upgrade_websocket(self):
            key = self.headers.get("Sec-WebSocket-Key")
            if not key or "upgrade" not in self.headers.get(
                    "Connection", "").lower():
                self.send_error(400, "not a websocket handshake")
                return
            accept = base64.b64encode(
                hashlib.sha1((key + WS_GUID).encode()).digest()
            ).decode()
            self.send_response(101, "Switching Protocols")
            self.send_header("Upgrade", "websocket")
            self.send_header("Connection", "Upgrade")
            self.send_header("Sec-WebSocket-Accept", accept)
            self.end_headers()
            self.close_connection = True
            conn = WSConn(self.connection, server)
            server._ws_register(conn)
            try:
                conn.serve()  # blocks for the life of the ws conn
            finally:
                server._ws_unregister(conn)

    return Handler


class WSConn:
    """One websocket client: JSON-RPC dispatch + event subscriptions
    (reference wsConnection + wsSubscribe in rpc/core/events.go).

    Event notifications go through a bounded send queue drained by a
    dedicated writer thread — a client that stops reading backs up its
    own queue only, and the configured slow policy (drop/disconnect)
    applies there. Direct RPC responses and pongs bypass the queue (a
    slow client stalls only its own request thread)."""

    def __init__(self, sock: socket.socket, server: RPCServer):
        self.sock = sock
        self.server = server
        self.env = server.env
        self._send_lock = threading.Lock()
        self._subscriber = f"ws-{id(self):x}-{time.monotonic_ns()}"
        self._subs: Dict[str, object] = {}  # query str -> Subscription
        self._pumps = []
        self._closed = threading.Event()
        # bounded event send queue + its writer
        self._q: collections.deque = collections.deque()
        self._q_cap = server.ws_send_queue
        self._q_cond = threading.Condition()
        self._q_hwm = 0
        # height of the newest NewBlock frame queued and not yet handed
        # to the writer (0: none); read only for rpc.wsSend's `height`
        self._block_height = 0
        self.events_sent = 0
        self.events_dropped = 0
        self._writer: Optional[threading.Thread] = None

    # -- frame IO ------------------------------------------------------

    def _recv_exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("ws closed")
            buf += chunk
        return buf

    def recv_frame(self) -> Optional[bytes]:
        """Returns a full text/binary message, None on close frame.
        Fragmented messages are reassembled; ping answered inline.
        Frames (and reassembled messages) over MAX_BODY_BYTES tear the
        connection down — the extended length field is wire input and
        must never size an allocation unchecked."""
        message = b""
        while True:
            hdr = self._recv_exact(2)
            fin = hdr[0] & 0x80
            opcode = hdr[0] & 0x0F
            masked = hdr[1] & 0x80
            ln = hdr[1] & 0x7F
            if ln == 126:
                ln = struct.unpack(">H", self._recv_exact(2))[0]
            elif ln == 127:
                ln = struct.unpack(">Q", self._recv_exact(8))[0]
            if ln + len(message) > MAX_BODY_BYTES:
                raise ConnectionError(
                    f"ws frame exceeds {MAX_BODY_BYTES} bytes")
            mask = self._recv_exact(4) if masked else b""
            payload = self._recv_exact(ln)
            if masked:
                payload = bytes(
                    b ^ mask[i % 4] for i, b in enumerate(payload))
            if opcode == 0x8:  # close
                return None
            if opcode == 0x9:  # ping → pong
                self.send_frame(payload, opcode=0xA)
                continue
            if opcode == 0xA:  # pong
                continue
            message += payload
            if fin:
                return message

    def send_frame(self, payload: bytes, opcode: int = 0x1) -> None:
        with self._send_lock:
            header = bytes([0x80 | opcode])
            ln = len(payload)
            if ln < 126:
                header += bytes([ln])
            elif ln < (1 << 16):
                header += bytes([126]) + struct.pack(">H", ln)
            else:
                header += bytes([127]) + struct.pack(">Q", ln)
            self.sock.sendall(header + payload)

    def send_json(self, obj: dict) -> None:
        self.send_bytes(jsonrpc.dumps(obj))

    def send_bytes(self, payload: bytes) -> None:
        try:
            self.send_frame(payload)
        except OSError:
            self._closed.set()

    # -- event send queue ----------------------------------------------

    def queue_depth(self) -> int:
        with self._q_cond:
            return len(self._q)

    def enqueue_event(self, frame: bytes) -> bool:
        """Queue one pre-rendered event frame for the writer. Applies
        the slow-client policy when the queue is full; returns False if
        the frame was shed (or the connection is closing)."""
        if self._closed.is_set():
            return False
        disconnect = False
        with self._q_cond:
            if len(self._q) >= self._q_cap:
                self.events_dropped += 1
                policy = self.server.ws_slow_policy
                self.server._note_dropped(policy)
                disconnect = policy == "disconnect"
            else:
                self._q.append(frame)
                self._q_hwm = max(self._q_hwm, len(self._q))
                self._q_cond.notify()
                self.server._note_enqueued()
                return True
        if disconnect:
            LOG.info("ws client too slow (queue %d full); disconnecting",
                     self._q_cap)
            self.close()
        return False

    # frames appended per enqueue_events lock hold: amortizes the queue
    # lock while still releasing it between chunks, so the writer
    # thread can interleave pops — a burst sheds only what the writer
    # genuinely can't drain (the per-frame enqueue_event behavior),
    # not deterministically everything past the cap
    ENQUEUE_CHUNK = 32

    def enqueue_events(self, frames, block_height: int = 0) -> int:
        """Queue a drained batch of pre-rendered frames in chunked lock
        holds. Per-frame semantics match enqueue_event: each frame past
        capacity is counted dropped INDIVIDUALLY (a burst shedding k
        frames bumps the counters by k), the writer can drain between
        chunks, and the disconnect policy trips on the first overflow.
        `block_height` is the NewBlock among them, for the writer's
        span. Returns the number queued."""
        if self._closed.is_set() or not frames:
            return 0
        disconnect = False
        accepted = 0
        dropped = 0
        for start in range(0, len(frames), self.ENQUEUE_CHUNK):
            chunk = frames[start:start + self.ENQUEUE_CHUNK]
            with self._q_cond:
                if block_height:
                    self._block_height = block_height
                chunk_accepted = 0
                for frame in chunk:
                    if len(self._q) >= self._q_cap:
                        dropped += 1
                        self.events_dropped += 1
                        if self.server.ws_slow_policy == "disconnect":
                            disconnect = True
                            break
                    else:
                        self._q.append(frame)
                        chunk_accepted += 1
                if chunk_accepted:
                    self._q_hwm = max(self._q_hwm, len(self._q))
                    self._q_cond.notify()
                    accepted += chunk_accepted
            if disconnect:
                break
        if dropped:
            self.server._note_dropped(self.server.ws_slow_policy, dropped)
        if accepted:
            self.server._note_enqueued(accepted)
        if disconnect:
            LOG.info("ws client too slow (queue %d full); disconnecting",
                     self._q_cap)
            self.close()
        return accepted

    def _writer_loop(self) -> None:
        while True:
            with self._q_cond:
                while not self._q and not self._closed.is_set():
                    self._q_cond.wait(timeout=0.5)
                if self._closed.is_set() and not self._q:
                    return
                frame = self._q.popleft()
                height, self._block_height = self._block_height, 0
            # rpc.wsSend: one span per burst — from the first frame
            # popped until the queue is found empty — never one per frame
            # and never across the wait above
            frames = nbytes = 0
            with tracing.span("rpc.wsSend", cat="rpc") as sp:
                try:
                    while frame is not None:
                        self.send_frame(frame)
                        self.events_sent += 1
                        frames += 1
                        nbytes += len(frame)
                        with self._q_cond:
                            frame = self._q.popleft() if self._q else None
                            height = self._block_height or height
                            self._block_height = 0
                except OSError:
                    self._closed.set()
                    with self._q_cond:
                        self._q.clear()
                        self._q_cond.notify_all()
                    return
                finally:
                    sp.set(frames=frames, bytes=nbytes,
                           **({"height": height} if height else {}))

    # -- serve loop ----------------------------------------------------

    def serve(self) -> None:
        self._writer = threading.Thread(
            target=self._writer_loop, daemon=True,
            name=f"ws-writer-{id(self):x}")
        self._writer.start()
        try:
            while not self._closed.is_set():
                msg = self.recv_frame()
                if msg is None:
                    break
                try:
                    req = jsonrpc.loads(msg)
                except RPCError as e:
                    self.send_json(
                        jsonrpc.error_response(None, e.code, e.message))
                    continue
                self._dispatch(req)
        except (ConnectionError, OSError):
            pass
        finally:
            self._closed.set()
            with self._q_cond:
                self._q_cond.notify_all()
            self.env.event_bus.unsubscribe_all(self._subscriber)
            self.server._note_subs(-len(self._subs))
            self._subs.clear()
            try:
                self.sock.close()
            except OSError:
                pass

    def close(self) -> None:
        """Tear the connection down from outside (server stop, slow-
        client disconnect): a FIN reaches the client so its read loop
        exits promptly."""
        self._closed.set()
        with self._q_cond:
            self._q_cond.notify_all()
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass

    def _dispatch(self, req: dict) -> None:
        if not isinstance(req, dict) or "method" not in req:
            return self.send_json(jsonrpc.error_response(
                None, jsonrpc.ERR_INVALID_REQUEST, "invalid request"))
        id_ = req.get("id")
        method = req["method"]
        params = req.get("params") or {}
        try:
            if method == "subscribe":
                self.send_json(jsonrpc.ok_response(
                    id_, self._subscribe(params)))
            elif method == "unsubscribe":
                self.send_json(jsonrpc.ok_response(
                    id_, self._unsubscribe(params)))
            elif method == "unsubscribe_all":
                self.env.event_bus.unsubscribe_all(self._subscriber)
                self.server._note_subs(-len(self._subs))
                self._subs.clear()
                self.send_json(jsonrpc.ok_response(id_, {}))
            else:
                raw = self.server.call_bytes(method, params)
                self.send_bytes(_result_frame(id_, raw))
        except RPCError as e:
            self.send_json(jsonrpc.error_response(id_, e.code, e.message))
        except Exception as e:  # noqa: BLE001
            LOG.exception("ws rpc %s failed", method)
            self.send_json(
                jsonrpc.error_response(id_, jsonrpc.ERR_INTERNAL, str(e)))

    # -- subscriptions (rpc/core/events.go Subscribe) ------------------

    def _subscribe(self, params: dict) -> dict:
        qs = params.get("query")
        if not qs:
            raise RPCError(jsonrpc.ERR_INVALID_PARAMS, "missing query")
        if qs in self._subs:
            raise RPCError(jsonrpc.ERR_SERVER, "already subscribed")
        sub = self.env.event_bus.subscribe(self._subscriber, Query(qs), 128)
        self._subs[qs] = sub
        self.server._note_subs(1)
        t = threading.Thread(
            target=self._pump, args=(qs, sub), daemon=True,
            name=f"ws-sub-{len(self._subs)}",
        )
        t.start()
        self._pumps.append(t)
        return {}

    def _unsubscribe(self, params: dict) -> dict:
        qs = params.get("query")
        if not qs or qs not in self._subs:
            raise RPCError(jsonrpc.ERR_SERVER, "subscription not found")
        self.env.event_bus.unsubscribe(self._subscriber, Query(qs))
        if self._subs.pop(qs, None) is not None:
            self.server._note_subs(-1)
        return {}

    def _pump(self, qs: str, sub) -> None:
        """Move matching events from the bus subscription into this
        client's send queue, a drained batch at a time: payloads are
        rendered ONCE per event process-wide (render_event_frames
        memoizes data+tags on the Message, taking the render lock once
        per batch instead of once per tx); this pump only splices the
        query string and enqueues the batch under one queue-lock
        acquisition."""
        from .core import render_event_frames

        while not self._closed.is_set() and not sub.cancelled:
            msgs = sub.get_batch(256, timeout=0.5)
            if not msgs:
                continue
            height = 0
            if tracing.get_tracer().enabled:
                for m in msgs:
                    if m.tags.get("tm.event") == "NewBlock":
                        height = m.data["block"].header.height
            self.enqueue_events(render_event_frames(msgs, qs), height)
