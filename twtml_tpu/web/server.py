"""Dashboard web server (reference: twtml-web's Socko server, Server.scala +
ApiHandler.scala).

Same route surface and broadcast semantics as the reference:

- ``POST /api``        → cache payload, respond ``{"status":"OK"}``, broadcast
                         the raw JSON to every live websocket
                         (ApiHandler.scala:50-57);
- ``GET /api/config``  → cached Config JSON (ApiHandler.scala:38-42);
- ``GET /api/stats``   → cached Stats JSON (ApiHandler.scala:44-48);
- ``WS /api``          → on connect, push the cached Config to the new socket
                         (ApiHandler.scala:68-73); every inbound frame is
                         cached and broadcast to ALL sockets including the
                         sender (ApiHandler.scala:59-67);
- ``GET /``            → dashboard index, ``GET /*`` → static assets
                         (Server.scala:54-59), 404 otherwise.

Netty/Akka actors become one asyncio event loop (aiohttp); the per-message
fire-once actor pattern is just a coroutine per request. ``start_background``
runs the loop in a daemon thread so tests and the training CLI can embed the
server in-process — the pattern the reference's WebTestSuite used by calling
Main.main directly (WebTestSuite.scala:22).
"""

from __future__ import annotations

import asyncio
import json
import mimetypes
import threading
from importlib import resources as _res

from aiohttp import WSMsgType, web

from ..utils import get_logger
from .cache import ApiCache

log = get_logger("web.server")

OK = json.dumps({"status": "OK"})


class Server:
    def __init__(self, port: int = 8888, host: str = "0.0.0.0",
                 cache: ApiCache | None = None):
        self.port = port
        self.host = host
        self.cache = cache if cache is not None else ApiCache()
        self._websockets: set[web.WebSocketResponse] = set()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self._runner: web.AppRunner | None = None
        self._started = threading.Event()
        self._assets = _res.files("twtml_tpu.web").joinpath("assets")
        # serving front door (ISSUE 9): a ServingPlane attached by the
        # serve entry point makes POST /api/predict live; without one the
        # route answers 503 (this process has no model)
        self._serving = None
        # fleet front door (ISSUE 11): a FleetRouter attached by the router
        # entry point makes POST /api/predict a forwarding proxy over the
        # replica fleet and GET /api/fleet a LIVE router view
        self._fleet = None

    def attach_serving(self, plane) -> "Server":
        """Attach a ``serving.ServingPlane``: POST /api/predict submits to
        its coalescer and awaits the pipelined result future."""
        self._serving = plane
        return self

    def attach_fleet(self, router) -> "Server":
        """Attach a ``serving.FleetRouter``: POST /api/predict forwards to
        a replica per the route policy (failed replicas eject + retry on
        another — the client never sees a single replica's death), and
        GET /api/fleet answers with live router stats."""
        self._fleet = router
        return self

    # -- handlers ------------------------------------------------------------
    async def _post_api(self, request: web.Request) -> web.StreamResponse:
        text = await request.text()
        log.debug("http - post data %s", text)
        self.cache.cache(text)
        await self._broadcast(text)
        return web.Response(text=OK, content_type="application/json")

    async def _get_config(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.config(), content_type="application/json")

    async def _get_stats(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.stats(), content_type="application/json")

    async def _get_series(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.series(), content_type="application/json")

    async def _get_metrics(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.metrics(), content_type="application/json")

    async def _get_hosts(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.hosts(), content_type="application/json")

    async def _get_tenants(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.tenants(),
                            content_type="application/json")

    async def _get_model(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.model(),
                            content_type="application/json")

    async def _get_serving(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.serving(),
                            content_type="application/json")

    async def _get_freshness(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.freshness(),
                            content_type="application/json")

    async def _get_history(self, request: web.Request) -> web.StreamResponse:
        return web.Response(text=self.cache.history(),
                            content_type="application/json")

    async def _get_fleet(self, request: web.Request) -> web.StreamResponse:
        # a router process answers LIVE (the view is plain host bookkeeping
        # under a lock); any other process serves the cached additive view
        if self._fleet is not None:
            view = {"jsonClass": "Fleet", **self._fleet.stats()}
            return web.Response(text=json.dumps(view),
                                content_type="application/json")
        return web.Response(text=self.cache.fleet(),
                            content_type="application/json")

    async def _post_predict(self, request: web.Request) -> web.StreamResponse:
        """The serving front door: coalesced, pipelined inference from the
        attached plane's device-resident snapshot. Errors are JSON with an
        ``error`` field — 503 when no plane is attached or the plane
        aborted (wedged transport → watchdog abort, never a hang), 400 on a
        malformed request body."""
        def fail(status: int, message: str) -> web.Response:
            return web.Response(
                text=json.dumps({"error": message}), status=status,
                content_type="application/json",
            )

        if self._fleet is not None:
            # fleet front door: forward the raw body off the event loop
            # (urllib blocks; the executor bounds concurrency) — replica
            # failures retry/eject inside the router, so a client only
            # sees 503 when the whole fleet is down this instant
            body = await request.read()
            loop = asyncio.get_event_loop()
            # the router's OWN forward pool: asyncio's default executor is
            # cpu+4 threads, which on a small host would cap a whole
            # fleet near one replica's in-flight budget
            status, payload = await loop.run_in_executor(
                getattr(self._fleet, "executor", None),
                self._fleet.predict, body,
            )
            return web.Response(
                body=payload, status=status,
                content_type="application/json",
            )
        plane = self._serving
        if plane is None:
            return fail(503, "serving not enabled on this server "
                             "(start via twtml_tpu.apps.serve or route a "
                             "fleet via twtml_tpu.apps.router)")
        try:
            payload = json.loads(await request.text())
            rows = payload["rows"] if isinstance(payload, dict) else payload
            if not isinstance(rows, list):
                raise ValueError("body must be {\"rows\": [...]} ")
            statuses = plane.statuses_from_rows(rows)
        except (ValueError, KeyError, TypeError) as exc:
            return fail(400, f"bad predict request: {exc}")
        try:
            # the plane's future resolves from the pipelined fetch pool;
            # wrap_future bridges it into this event loop. The
            # FetchWatchdog bounds how long it can possibly take.
            result = await asyncio.wrap_future(plane.submit(statuses))
        except ValueError as exc:  # oversized request
            return fail(400, str(exc))
        except Exception as exc:
            return fail(503, str(exc))
        return web.Response(
            text=json.dumps({
                "predictions": result["predictions"],
                "snapshotStep": result["snapshot_step"],
                "servedRows": len(result["predictions"]),
                # dispatch-time snapshot age (ISSUE 16): how stale the
                # weights that scored THIS response were; -1 from planes
                # predating the freshness stamp (fleet replicas mid-roll)
                "modelStalenessS": result.get("model_staleness_s", -1.0),
            }),
            content_type="application/json",
        )

    async def _ws_api(self, request: web.Request) -> web.StreamResponse:
        ws = web.WebSocketResponse(heartbeat=30)
        await ws.prepare(request)
        self._websockets.add(ws)
        log.debug("websocket connected (%d live)", len(self._websockets))
        try:
            await ws.send_str(self.cache.config())  # WsStartHandler behavior
            async for msg in ws:
                if msg.type == WSMsgType.TEXT:
                    self.cache.cache(msg.data)
                    await self._broadcast(msg.data)
                elif msg.type == WSMsgType.ERROR:
                    break
        finally:
            self._websockets.discard(ws)
        return ws

    async def _broadcast(self, text: str) -> None:
        """Fan a frame out to every dashboard (webSocketConnections.writeText
        equivalent); dead sockets are dropped silently."""
        for ws in list(self._websockets):
            try:
                await ws.send_str(text)
            except Exception:
                self._websockets.discard(ws)

    async def _index(self, request: web.Request) -> web.StreamResponse:
        return self._static_file("index.html")

    async def _static(self, request: web.Request) -> web.StreamResponse:
        rel = request.match_info["path"]
        return self._static_file(rel)

    def _static_file(self, rel: str) -> web.StreamResponse:
        # join segment-by-segment with every segment vetted: a single
        # joinpath("/abs/path") would DISCARD the assets base entirely
        # (pathlib semantics; "D:" does the same on Windows) and serve
        # arbitrary filesystem paths. Control chars (e.g. %00) would raise
        # from is_file() → 500; they 404 here instead.
        parts = rel.split("/")
        if any(
            p in ("", ".", "..")
            or "\\" in p
            or ":" in p
            or any(ord(c) < 32 for c in p)
            for p in parts
        ):
            raise web.HTTPNotFound
        parent = self._assets
        for p in parts[:-1]:
            parent = parent.joinpath(p)
        target = parent.joinpath(parts[-1])
        if rel.endswith(".js") and not rel.endswith(".min.js"):
            # dist builds ship minified assets (tools/jsminify.py via
            # scripts/build_dist.sh — the reference's sbt-uglify analog,
            # web/build.sbt:25-39): serve file.min.js when present, so the
            # dashboard loads the minified bundle without URL changes.
            # Staleness guard for dev trees: a leftover (gitignored)
            # .min.js older than an edited source must not shadow the fix;
            # when mtimes are unavailable (zip deploys — immutable), the
            # minified file wins.
            minified = parent.joinpath(parts[-1][:-3] + ".min.js")
            if minified.is_file():
                try:
                    import os as _os

                    fresh = _os.path.getmtime(str(minified)) >= (
                        _os.path.getmtime(str(target))
                    )
                except OSError:
                    fresh = True
                if fresh:
                    target = minified
        if not target.is_file():
            raise web.HTTPNotFound
        ctype, _ = mimetypes.guess_type(rel)
        return web.Response(body=target.read_bytes(),
                            content_type=ctype or "application/octet-stream")

    # -- lifecycle -----------------------------------------------------------
    def _build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/api", self._ws_api)  # websocket handshake
        app.router.add_post("/api", self._post_api)
        app.router.add_get("/api/config", self._get_config)
        app.router.add_get("/api/stats", self._get_stats)
        app.router.add_get("/api/series", self._get_series)  # chart backfill
        app.router.add_get("/api/metrics", self._get_metrics)  # observability
        app.router.add_get("/api/hosts", self._get_hosts)  # lockstep fleet view
        app.router.add_get("/api/tenants", self._get_tenants)  # model plane
        app.router.add_get("/api/model", self._get_model)  # model health
        app.router.add_get("/api/serving", self._get_serving)  # serve plane
        app.router.add_get("/api/fleet", self._get_fleet)  # read fleet
        app.router.add_get("/api/freshness", self._get_freshness)  # e2e lag
        app.router.add_get("/api/history", self._get_history)  # historian
        app.router.add_post("/api/predict", self._post_predict)  # front door
        app.router.add_get("/", self._index)
        app.router.add_get("/{path:.+}", self._static)
        return app

    async def _start_async(self) -> None:
        self._runner = web.AppRunner(self._build_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        log.info("Open your browser and navigate to http://%s:%d",
                 self.host, self.port)

    async def _stop_async(self) -> None:
        for ws in list(self._websockets):
            try:
                await ws.close()
            except Exception:  # lawcheck: disable=TW005 -- best-effort websocket close on shutdown; a dead client must not wedge server stop
                pass
        if self._runner is not None:
            await self._runner.cleanup()

    def start_background(self) -> "Server":
        """Run the server loop in a daemon thread; returns once listening."""
        def runner():
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)
            self._loop.run_until_complete(self._start_async())
            self._started.set()
            self._loop.run_forever()

        self._thread = threading.Thread(target=runner, name="twtml-web", daemon=True)
        self._thread.start()
        if not self._started.wait(timeout=10):
            raise RuntimeError("web server failed to start")
        return self

    def stop(self) -> None:
        if self._loop is None:
            return
        fut = asyncio.run_coroutine_threadsafe(self._stop_async(), self._loop)
        try:
            fut.result(timeout=5)
        except Exception:  # lawcheck: disable=TW005 -- best-effort bounded shutdown: a wedged event loop is abandoned (daemon thread) rather than hanging the app exit
            pass
        self._loop.call_soon_threadsafe(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5)

    def run_forever(self) -> None:
        """Foreground mode for the standalone process (web.main)."""
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        loop.run_until_complete(self._start_async())
        try:
            loop.run_forever()
        finally:
            loop.run_until_complete(self._stop_async())
