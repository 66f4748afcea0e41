"""Feeder: a child process that serves the seeded pool of status lines as
the v1.1 streaming endpoint does and never imports jax.

HTTP/1.1, ``Transfer-Encoding: chunked``, one JSON object per ``\\r\\n``-
delimited line. The pool is framed ONCE into chunks of ``chunk_bytes``; a
connection then gets the framed buffer in a loop, forever, so the stream
never ends and the trainer never reconnects inside a window. The socket is
non-blocking: the time spent waiting for it to take more (``select``) is the
feeder's BLOCKED time, apart from the time spent copying, so a trainer that
pushes back shows as a high blocked share and a starved one as a low share.

Talks to its parent on stdout, one JSON object per line:
  {"ready": port, "lines": n, "bytes": n, "gen_s": s}
  {"t": monotonic, "conn": k, "blocked_s": s, "sent": bytes}   (every 50 ms)
"""

from __future__ import annotations

import argparse
import json
import select
import socket
import sys
import time

SAMPLE_S = 0.05
SEND_BYTES = 1 << 20


def build_body(traffic: dict, seed: int) -> tuple[bytes, int]:
    from . import gen

    g = traffic["generator"]
    vocab = gen.build_vocab(g, seed)
    n = int(g["pool_lines"])
    parts = []
    for c in range((n + gen.CHUNK - 1) // gen.CHUNK):
        ch = gen.make_chunk(g, vocab, seed, c, min(gen.CHUNK, n - c * gen.CHUNK))
        parts.append(("\r\n".join(ch.lines) + "\r\n").encode("ascii"))
    return b"".join(parts), n


def frame(body: bytes, chunk_bytes: int) -> bytes:
    out = []
    for i in range(0, len(body), chunk_bytes):
        piece = body[i:i + chunk_bytes]
        out.append(b"%x\r\n" % len(piece) + piece + b"\r\n")
    return b"".join(out)


def say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def serve(conn: socket.socket, framed: memoryview, k: int) -> None:
    """Read the request head, answer 200 chunked, then stream until the
    peer hangs up."""
    conn.settimeout(10.0)
    head = b""
    while b"\r\n\r\n" not in head:
        data = conn.recv(65536)
        if not data:
            return
        head += data
    conn.sendall(
        b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
        b"Transfer-Encoding: chunked\r\n\r\n"
    )
    conn.setblocking(False)
    pos, total, sent, blocked = 0, len(framed), 0, 0.0
    next_say = time.monotonic()
    while True:
        t0 = time.monotonic()
        if t0 >= next_say:
            say({"t": t0, "conn": k, "blocked_s": blocked, "sent": sent})
            next_say = t0 + SAMPLE_S
        try:
            n = conn.send(framed[pos:pos + SEND_BYTES])
        except BlockingIOError:
            select.select([], [conn], [], SAMPLE_S)
            blocked += time.monotonic() - t0
            continue
        except (BrokenPipeError, ConnectionResetError):
            say({"t": time.monotonic(), "conn": k, "blocked_s": blocked,
                 "sent": sent, "closed": True})
            return
        sent += n
        pos += n
        if pos >= total:
            pos = 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args(argv)
    with open(a.traffic, encoding="utf-8") as fh:
        traffic = json.load(fh)
    t0 = time.monotonic()
    body, n_lines = build_body(traffic, a.seed)
    framed = memoryview(frame(body, int(traffic["feeder"]["chunk_bytes"])))
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    say({"ready": srv.getsockname()[1], "lines": n_lines, "bytes": len(body),
         "gen_s": time.monotonic() - t0})
    k = 0
    while True:  # one consumer at a time; the parent ends us with SIGTERM
        conn, _ = srv.accept()
        with conn:
            try:
                serve(conn, framed, k)
            except OSError:
                pass
        k += 1


if __name__ == "__main__":
    sys.exit(main())
