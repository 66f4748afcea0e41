"""Stats sink: a child process that receives the trainer's per-batch POSTs
(``--twtweb``) and never imports jax.

Answers every request ``200 {}`` and, for each ``{"jsonClass": "Stats"}``
body, stamps the arrival on its own monotonic clock (CLOCK_MONOTONIC, one
clock for every process of the machine) and tells the parent on stdout:
  {"ready": port}
  {"t": monotonic, "count": cumulative_tweets, "batch": rows, "mse": m}
The window and both training metrics are computed from these lines alone:
the client's side of the system.
"""

from __future__ import annotations

import json
import socket
import sys
import threading
import time

REPLY = (b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
         b"Content-Length: 2\r\nConnection: close\r\n\r\n{}")


def read_request(conn: socket.socket) -> bytes:
    buf = b""
    while b"\r\n\r\n" not in buf:
        data = conn.recv(65536)
        if not data:
            return b""
        buf += data
    head, _, body = buf.partition(b"\r\n\r\n")
    length = 0
    for line in head.split(b"\r\n")[1:]:
        key, _, value = line.partition(b":")
        if key.strip().lower() == b"content-length":
            length = int(value)
    while len(body) < length:
        data = conn.recv(65536)
        if not data:
            break
        body += data
    return body


def handle(conn: socket.socket, out, lock: threading.Lock) -> None:
    with conn:
        conn.settimeout(2.0)
        try:
            body = read_request(conn)
            t = time.monotonic()
            conn.sendall(REPLY)
        except OSError:
            return
    if body.startswith(b'{"jsonClass": "Stats"'):
        s = json.loads(body)
        line = json.dumps({"t": t, "count": s["count"], "batch": s["batch"],
                           "mse": s["mse"]}) + "\n"
        with lock:
            out.write(line)
            out.flush()


def main() -> int:
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(64)
    out, lock = sys.stdout, threading.Lock()
    out.write(json.dumps({"ready": srv.getsockname()[1]}) + "\n")
    out.flush()
    # One thread a connection: a connection that sends nothing would
    # otherwise hold every later POST for its 2 s timeout, and the stall
    # would be the sink's, read as the trainer's (seen: one 2.2-2.5 s gap
    # in 2 of 27 runs, the publisher's span waiting that long for a reply).
    # The publisher sends a batch's POST after the last one's reply, so the
    # stamps keep the batches' order.
    while True:  # the parent ends us with SIGTERM
        conn, _ = srv.accept()
        threading.Thread(target=handle, args=(conn, out, lock),
                         daemon=True).start()


if __name__ == "__main__":
    sys.exit(main())
