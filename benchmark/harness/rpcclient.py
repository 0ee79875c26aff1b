"""The client's side of the node's RPC port: JSON-RPC over HTTP with
keep-alive, and a websocket subscription. Written against the wire
format, not the program's client classes."""

from __future__ import annotations

import base64
import hashlib
import http.client
import json
import os
import socket
import struct
import threading

_WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"


class RpcError(RuntimeError):
    pass


class Rpc:
    """One HTTP/1.1 connection; not shared between threads."""

    def __init__(self, addr: str, timeout: float = 60.0):
        host, _, port = addr.rpartition(":")
        self.host, self.port, self.timeout = host, int(port), timeout
        self.conn = None
        self._id = 0

    def post(self, body: bytes):
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(self.host, self.port,
                                                       timeout=self.timeout)
            try:
                self.conn.request("POST", "/", body=body,
                                  headers={"Content-Type": "application/json"})
                return json.loads(self.conn.getresponse().read())
            except (http.client.HTTPException, ConnectionError, socket.timeout):
                self.close()
                if attempt:
                    raise

    def call(self, method: str, params: dict | None = None):
        self._id += 1
        out = self.post(json.dumps({"jsonrpc": "2.0", "id": self._id,
                                    "method": method,
                                    "params": params or {}}).encode())
        if out.get("error"):
            raise RpcError(f"{method}: {out['error']}")
        return out.get("result")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def batch_body(method: str, txs: list) -> bytes:
    """One JSON-RPC batch POST of `method` calls, a tx each."""
    return json.dumps([
        {"jsonrpc": "2.0", "id": i, "method": method,
         "params": {"tx": base64.b64encode(tx).decode()}}
        for i, tx in enumerate(txs)]).encode()


class Subscription(threading.Thread):
    """A websocket subscription; `on_event(result, arrival)` runs on the
    reader thread with the host clock read when the frame was complete."""

    def __init__(self, addr: str, query: str, on_event, clock):
        super().__init__(name="bench-ws", daemon=True)
        host, _, port = addr.rpartition(":")
        self.sock = socket.create_connection((host, int(port)), timeout=30)
        key = base64.b64encode(os.urandom(16)).decode()
        self.sock.sendall((
            f"GET /websocket HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Upgrade: websocket\r\nConnection: Upgrade\r\n"
            f"Sec-WebSocket-Key: {key}\r\nSec-WebSocket-Version: 13\r\n\r\n"
        ).encode())
        buf = b""
        while b"\r\n\r\n" not in buf:
            chunk = self.sock.recv(4096)
            if not chunk:
                raise ConnectionError("websocket handshake failed")
            buf += chunk
        accept = base64.b64encode(hashlib.sha1((key + _WS_GUID).encode()).digest())
        if b"101" not in buf.split(b"\r\n", 1)[0] or accept not in buf:
            raise ConnectionError(f"websocket handshake refused: {buf[:80]!r}")
        self.sock.settimeout(None)
        self.on_event, self.clock = on_event, clock
        self.subscribed = threading.Event()
        self.closed = threading.Event()
        self.start()
        self._send(json.dumps({"jsonrpc": "2.0", "id": 1, "method": "subscribe",
                               "params": {"query": query}}).encode())
        if not self.subscribed.wait(30):
            raise ConnectionError("subscribe was not answered")

    def _send(self, payload: bytes, opcode: int = 0x1) -> None:
        mask = os.urandom(4)
        n = len(payload)
        head = bytes([0x80 | opcode])
        if n < 126:
            head += bytes([0x80 | n])
        elif n < 65536:
            head += bytes([0x80 | 126]) + struct.pack(">H", n)
        else:
            head += bytes([0x80 | 127]) + struct.pack(">Q", n)
        body = bytes(b ^ mask[i % 4] for i, b in enumerate(payload))
        self.sock.sendall(head + mask + body)

    def _exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(min(1 << 20, n - len(buf)))
            if not chunk:
                raise ConnectionError("websocket closed")
            buf += chunk
        return bytes(buf)

    def run(self) -> None:
        try:
            message = b""
            while not self.closed.is_set():
                hdr = self._exact(2)
                n = hdr[1] & 0x7F
                if n == 126:
                    n = struct.unpack(">H", self._exact(2))[0]
                elif n == 127:
                    n = struct.unpack(">Q", self._exact(8))[0]
                payload = self._exact(n)
                opcode = hdr[0] & 0x0F
                if opcode == 0x8:
                    break
                if opcode == 0x9:
                    self._send(payload, 0xA)
                    continue
                if opcode == 0xA:
                    continue
                message += payload
                if not hdr[0] & 0x80:
                    continue
                arrival = self.clock()
                obj, message = json.loads(message), b""
                if obj.get("id") == "#event":
                    self.on_event(obj.get("result") or {}, arrival)
                else:
                    self.subscribed.set()
        except (ConnectionError, OSError):
            pass
        finally:
            self.closed.set()

    def close(self) -> None:
        self.closed.set()
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()
        self.join(5)
