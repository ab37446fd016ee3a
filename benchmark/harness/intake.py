"""A loopback Datadog intake: a child process that never imports JAX.

A threaded HTTP server that stores each POSTed body with the wall-clock
time its last byte arrived and answers 202. It decodes nothing while the
window is open. The parent asks `GET /dump` once, after the window:
every stored body as one length-prefixed binary stream, which
`parse_dump` and `decode_series` (run in the parent) take apart; a body
`decode_series` cannot parse is counted there.

Run as `python intake.py`: prints `{"port": N}` and serves until stdin
closes.
"""

from __future__ import annotations

import gzip
import json
import struct
import sys
import threading
import time
import zlib
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

_HEAD = struct.Struct("<dII")   # arrival, bytes of path+encoding, of body


class Store:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._items: list = []

    def add(self, arrival: float, path: str, encoding: str,
            body: bytes) -> None:
        with self._lock:
            self._items.append((arrival, path, encoding, body))

    def dump(self) -> bytes:
        with self._lock:
            items = list(self._items)
        out = []
        for arrival, path, encoding, body in items:
            head = f"{path}\n{encoding}".encode()
            out += [_HEAD.pack(arrival, len(head), len(body)), head, body]
        return b"".join(out)


def parse_dump(blob: bytes) -> list:
    """-> [(arrival_unix, path, encoding, body)] as `Store.dump` wrote."""
    out, at = [], 0
    while at < len(blob):
        arrival, n_head, n_body = _HEAD.unpack_from(blob, at)
        at += _HEAD.size
        path, encoding = blob[at:at + n_head].decode().split("\n", 1)
        at += n_head
        out.append((arrival, path, encoding, blob[at:at + n_body]))
        at += n_body
    return out


def decode_series(encoding: str, body: bytes):
    """The `series` list of one POST /api/v1/series body, or None where
    it cannot be parsed."""
    try:
        if encoding == "gzip":
            body = gzip.decompress(body)
        elif encoding == "deflate":
            body = zlib.decompress(body)
        series = json.loads(body)["series"]
        return series if isinstance(series, list) else None
    except Exception:
        return None


def make_handler(store: Store):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args) -> None:
            pass

        def _answer(self, status: int, body: bytes,
                    content_type: str = "application/json") -> None:
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self) -> None:
            body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
            store.add(time.time(), self.path.split("?", 1)[0],
                      self.headers.get("Content-Encoding", ""), body)
            self._answer(202, b'{"status":"ok"}')

        def do_GET(self) -> None:
            if self.path == "/dump":
                self._answer(200, store.dump(), "application/octet-stream")
            else:
                self._answer(404, b"{}")

    return Handler


def main() -> int:
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(Store()))
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    print(json.dumps({"port": httpd.server_address[1]}), flush=True)
    sys.stdin.read()   # until the parent closes it
    httpd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
