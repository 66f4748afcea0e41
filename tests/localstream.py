"""In-process v1.1-protocol stream server: the live protocol without
Twitter credentials or egress, for the tests that drive the real
TwitterSource path (tests/test_twitter_live.py). The benchmark's own feeder
is benchmark/feeder.py.

Same protocol shape as the reference's endpoint (chunked HTTP/1.1,
delimited JSON lines, keep-alive blanks — what Twitter4j consumes at
LinearRegression.scala:44): the client exercises its full native stack
(OAuth1 signing, chunked decode, line reassembly, Status parse).
"""

from __future__ import annotations

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


class LocalV11StreamServer:
    """Serves ``lines`` (JSON tweet strings) as one chunked stream per
    connection, then a clean terminator; reconnects replay the corpus
    (the consumer's batch cap decides when the run ends)."""

    def __init__(self, lines: list[str], chunk_bytes: int = 1 << 14):
        body = ("\r\n".join(lines) + "\r\n").encode()
        chunk = chunk_bytes

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):  # quiet
                pass

            def do_GET(self):
                self.send_response(200)
                self.send_header("Transfer-Encoding", "chunked")
                self.send_header("Content-Type", "application/json")
                self.end_headers()
                try:
                    for i in range(0, len(body), chunk):
                        piece = body[i : i + chunk]
                        self.wfile.write(
                            f"{len(piece):x}\r\n".encode() + piece + b"\r\n"
                        )
                    self.wfile.write(b"0\r\n\r\n")
                except (BrokenPipeError, ConnectionResetError):
                    pass  # consumer hit its cap and hung up
                self.close_connection = True

        self._server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True
        )

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self._server.server_address[1]}/stream"

    def __enter__(self) -> "LocalV11StreamServer":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._server.shutdown()
        self._server.server_close()
