"""The shipped dashboard JavaScript, EXECUTED (VERDICT r1 #5).

The reference declared browser tests and commented them out
(WebTestSuite.scala:7,44-52); this build image has no JS runtime at all, so
these tests run the REAL asset files (web/assets/js/*.js, untouched) on the
in-repo jsmini interpreter (tools/jsmini.py) against a stub DOM whose
elements come from the REAL index.html/test.html id attributes
(tools/jsdom.py). A broken jsonClass dispatch, a renamed counter id, or a
syntax error in any shipped asset fails here. Parsing every file also
replaces the reference's sbt-jshint asset lint (web/build.sbt:25-39).
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.jsdom import Harness  # noqa: E402
from tools.jsmini import parse  # noqa: E402

ASSETS = os.path.join(REPO, "twtml_tpu", "web", "assets")
JS = os.path.join(ASSETS, "js")
ALL_JS = ["api.js", "chart.js", "index.js", "test.js"]


def js_path(name):
    return os.path.join(JS, name)


# ---------------------------------------------------------------------------
# lint: every shipped asset parses (the sbt-jshint analog)

@pytest.mark.parametrize("name", ALL_JS)
def test_shipped_js_parses(name):
    with open(js_path(name), encoding="utf-8") as fh:
        parse(fh.read())


# ---------------------------------------------------------------------------
# dashboard page (index.html + api.js + chart.js + index.js)

def dashboard(defer_series=False):
    h = Harness([os.path.join(ASSETS, "index.html")])
    h.fetch_routes["/api/stats"] = {
        "jsonClass": "Stats", "count": 0, "batch": 0, "mse": 0,
        "realStddev": 0, "predStddev": 0,
    }
    h.fetch_routes["/api/hosts"] = {
        "jsonClass": "Hosts", "hosts": [], "straggler": -1, "stage": "",
        "skewMs": 0.0,
    }
    h.fetch_routes["/api/tenants"] = {
        "jsonClass": "Tenants", "tenants": [], "gating": -1, "active": 0,
    }
    h.fetch_routes["/api/model"] = {
        "jsonClass": "ModelHealth", "level": "ok", "driftScore": 0.0,
        "lossTrend": 0.0, "weightNorm": 0.0, "updateNorm": 0.0,
        "gradNorm": 0.0, "mse": [], "tenants": [], "episodes": 0,
    }
    h.fetch_routes["/api/serving"] = {
        "jsonClass": "Serving", "qps": 0.0, "rowsPerSec": 0.0,
        "p50Ms": 0.0, "p95Ms": 0.0, "p99Ms": 0.0, "snapshotStep": -1,
        "level": "", "requests": 0, "rows": 0, "errors": 0, "tenants": [],
    }
    h.fetch_routes["/api/fleet"] = {
        "jsonClass": "Fleet", "policy": "", "replicas": [], "requests": 0,
        "retries": 0, "ejections": 0, "champion": -1,
    }
    h.fetch_routes["/api/freshness"] = {
        "jsonClass": "Freshness", "batches": 0, "rows": 0, "eventLagMs": -1.0,
        "eventLagP50Ms": -1.0, "eventLagP95Ms": -1.0, "eventLagP99Ms": -1.0,
        "publishLagP95Ms": -1.0, "watermarkLagMs": -1.0, "watermark": [],
        "critical": "", "criticalTicks": {}, "sloMs": 0.0, "breachRun": 0,
        "breaches": 0,
    }
    series = h.defer("/api/series") if defer_series else None
    if not defer_series:
        h.fetch_routes["/api/series"] = []
    for name in ("api.js", "chart.js", "index.js"):
        h.load_script(js_path(name))
    h.dom_content_loaded()
    return (h, series) if defer_series else h


def frame(**kw):
    return json.dumps(kw)


def test_boot_opens_websocket_and_backfills():
    h = dashboard()
    assert len(h.websockets) == 1
    assert h.ws.url == "ws://localhost:8888/api"
    urls = [u for u, _ in h.fetches]
    assert "/api/stats" in urls and "/api/series" in urls


def test_socket_badge_lifecycle():
    h = dashboard()
    h.ws.server_open()
    assert h.el("conn").text == "live"
    assert "live" in h.el("conn").class_set
    h.ws.server_close()
    assert h.el("conn").text == "offline"
    assert "live" not in h.el("conn").class_set


def test_stats_frame_updates_all_five_counters():
    """The five counter ids are the reference's wire contract
    (index.html:46-67, js/index.js:55-61)."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Stats", count=1234567, batch=678, mse=4321,
        realStddev=15, predStddev=25,
    ))
    assert h.el("count").text == "1,234,567"  # toLocaleString
    assert h.el("batch").text == "678"
    assert h.el("mse").text == "4,321"
    assert h.el("realStddev").text == "15"
    assert h.el("predStddev").text == "25"


def test_config_frame_resets_counters_and_rebuilds_iframes():
    """Config: counters reset, session label set, one iframe per viz id with
    the reference's pym URL shape (js/index.js:35-43)."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Stats", count=9, batch=9, mse=9, realStddev=9, predStddev=9,
    ))
    h.ws.server_message(frame(
        jsonClass="Config", id="sess-1", host="http://lightning",
        viz=["101", "102"],
    ))
    for el_id in ("count", "batch", "mse", "realStddev", "predStddev"):
        assert h.el(el_id).text == "0"
    assert h.el("session").text == "sess-1"
    frames = h.el("graphs").children
    assert [f.tag for f in frames] == ["iframe", "iframe"]
    assert [f.get("src") for f in frames] == [
        "http://lightning/visualizations/101/pym",
        "http://lightning/visualizations/102/pym",
    ]


def test_metrics_frame_updates_observability_panel():
    """Metrics frames (telemetry/metrics.py snapshots) drive the pipeline
    panel: fetch-health badge with phase class, rtt, wire MB, rss, fetch depth."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Metrics",
        counters={"wire.bytes": 2500000},
        gauges={"host.rss_mb": 512.5, "fetch.queue_depth": 7},
        health={"phase": "degraded", "rtt_ms": 412.5, "transitions": 3},
    ))
    assert h.el("fetchPhase").text == "degraded"
    assert "degraded" in h.el("fetchPhase").class_set
    assert h.el("rttMs").text == "412.5"
    assert h.el("wireMb").text == "2.5"
    assert h.el("rssMb").text == "512.5"
    assert h.el("fetchDepth").text == "7"
    assert h.el("phaseFlips").text == "3"
    # recovery flips the badge class back
    h.ws.server_message(frame(
        jsonClass="Metrics", counters={}, gauges={},
        health={"phase": "healthy", "rtt_ms": 71.0, "transitions": 4},
    ))
    assert h.el("fetchPhase").text == "healthy"
    assert "healthy" in h.el("fetchPhase").class_set
    assert "degraded" not in h.el("fetchPhase").class_set


def test_metrics_frame_updates_ingest_guard_tiles():
    """r7 ingest/state robustness tiles: queue depth (rows), shed rows,
    and sentinel rollbacks (highlighted once any occurred)."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Metrics",
        counters={"ingest.rows_shed": 4096, "model.rollbacks": 2},
        gauges={"ingest.queue_rows": 12288},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("queueRows").text == "12288"
    assert h.el("rowsShed").text == "4096"
    assert h.el("rollbacks").text == "2"
    assert "degraded" in h.el("rollbacks").class_set
    # a healthy run keeps the tile quiet
    h.ws.server_message(frame(
        jsonClass="Metrics", counters={}, gauges={},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("rollbacks").text == "0"
    assert "degraded" not in h.el("rollbacks").class_set


def test_metrics_frame_updates_journal_tile():
    """ISSUE 19 intake journal: the journal.replayed_rows counter renders on
    the 'journal · replayed' tile — nonzero means a recovery path replayed
    rows instead of counting them lost; a frame without it resets to 0."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Metrics",
        counters={"journal.replayed_rows": 2048},
        gauges={"journal.disk_mb": 12.5},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("journalReplayed").text == "2048"
    h.ws.server_message(frame(
        jsonClass="Metrics", counters={}, gauges={},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("journalReplayed").text == "0"


def test_metrics_frame_updates_wire_ratio_tile():
    """r15 compressed wire: the wire.codec_ratio gauge (raw/compressed
    units bytes, apps/common._record_wire_codec) renders on the pipeline
    panel; a frame without it resets the tile to 1.00 (codec off)."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Metrics",
        counters={"wire.codec_fallbacks": 0},
        gauges={"wire.codec_ratio": 1.472,
                "wire.units_compressed_bytes": 11264},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("wireRatio").text == "1.47"
    h.ws.server_message(frame(
        jsonClass="Metrics", counters={}, gauges={},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("wireRatio").text == "1.00"


def test_metrics_frame_updates_latency_tile():
    """r8: the derived fetch-latency p95 (Metrics.histograms, seconds)
    renders in ms on the pipeline panel."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Metrics", counters={}, gauges={},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
        histograms={"fetch.latency_s": {"count": 9, "mean": 0.07,
                    "p50": 0.064, "p95": 0.128, "p99": 0.256}},
    ))
    assert h.el("fetchP95").text == "128.0"
    # a Metrics frame without histograms resets the tile, never throws
    h.ws.server_message(frame(
        jsonClass="Metrics", counters={}, gauges={},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("fetchP95").text == "0.0"


def test_hosts_frame_builds_tiles_and_names_straggler():
    """r8 Hosts tiles: one tile per host from the sideband view, the
    gating host highlighted with the ladder stage, tick skew shown."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Hosts",
        hosts=[{"host": 0, "tick_prep_ms": 12.4},
               {"host": 1, "tick_prep_ms": 141.7}],
        straggler=1, stage="upload", skewMs=129.3,
    ))
    assert h.el("straggler").text == "host 1 · upload"
    assert "degraded" in h.el("straggler").class_set
    assert h.el("tickSkew").text == "129.3"
    tiles = h.el("hostsPanel").children
    assert len(tiles) == 2
    labels = [t.children[0].text for t in tiles]
    values = [t.children[1].text for t in tiles]
    assert labels == ["host 0", "host 1 · gating"]
    assert values == ["12 ms", "142 ms"]
    assert "gating" in tiles[1].class_set
    assert "gating" not in tiles[0].class_set
    # a healthy tick clears the highlight and rebuilds the tiles
    h.ws.server_message(frame(
        jsonClass="Hosts",
        hosts=[{"host": 0, "tick_prep_ms": 10.0},
               {"host": 1, "tick_prep_ms": 11.0}],
        straggler=-1, stage="", skewMs=1.0,
    ))
    assert h.el("straggler").text == "—"
    assert "degraded" not in h.el("straggler").class_set
    tiles = h.el("hostsPanel").children
    assert all("gating" not in t.class_set for t in tiles)


def test_hosts_frame_elastic_tile_shows_epoch_hosts_and_lead():
    """r20 lead election: the elastic tile names the CURRENT lead next to
    the epoch + live-host count (it moves only at a won election), and a
    non-elastic run (epoch -1 / leadUid -1) keeps the dashes."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Hosts", hosts=[], straggler=-1, stage="", skewMs=0.0,
        epoch=2, liveHosts=3, leadUid=1, departed=1, rejoined=0,
    ))
    assert h.el("elasticEpoch").text == "2 · 3 hosts · lead 1"
    assert h.el("elasticChurn").text == "1 / 0"
    # a post-election 1-host epoch: singular "host", the winner as lead
    h.ws.server_message(frame(
        jsonClass="Hosts", hosts=[], straggler=-1, stage="", skewMs=0.0,
        epoch=1, liveHosts=1, leadUid=1, departed=1, rejoined=0,
    ))
    assert h.el("elasticEpoch").text == "1 · 1 host · lead 1"
    # not elastic: epoch/leadUid -1 → dashes, no stray "lead" text
    h.ws.server_message(frame(
        jsonClass="Hosts", hosts=[], straggler=-1, stage="", skewMs=0.0,
        epoch=-1, liveHosts=0, leadUid=-1, departed=0, rejoined=0,
    ))
    assert h.el("elasticEpoch").text == "—"
    assert h.el("elasticChurn").text == "—"


def test_tenants_frame_builds_tiles_and_highlights_gating():
    """r10 Tenants tiles (ISSUE 7): one tile per tenant from the model-
    plane view, the gating (busiest) tenant highlighted, active count
    shown as active/configured."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Tenants",
        tenants=[{"tenant": 0, "rows": 1200, "batch": 96, "mse": 1234.5},
                 {"tenant": 1, "rows": 800, "batch": 0, "mse": -1.0},
                 {"tenant": 2, "rows": 2100, "batch": 160, "mse": 88.0}],
        gating=2, active=2,
    ))
    assert h.el("tenantsActive").text == "2 / 3"
    tiles = h.el("tenantsPanel").children
    assert len(tiles) == 3
    labels = [t.children[0].text for t in tiles]
    values = [t.children[1].text for t in tiles]
    assert labels == ["tenant 0", "tenant 1", "tenant 2 · gating"]
    # rows localized + mse shown only when finite (-1 = no finite sample)
    assert values == ["1,200 · mse 1235", "800", "2,100 · mse 88"]
    assert "gating" in tiles[2].class_set
    assert all("gating" not in t.class_set for t in tiles[:2])
    # an all-dry tick clears the highlight
    h.ws.server_message(frame(
        jsonClass="Tenants",
        tenants=[{"tenant": 0, "rows": 1200, "batch": 0, "mse": -1.0}],
        gating=-1, active=0,
    ))
    tiles = h.el("tenantsPanel").children
    assert all("gating" not in t.class_set for t in tiles)


def test_model_health_frame_updates_tiles_and_level_class():
    """r11 "model · drift" tiles (ISSUE 8): health badge with graduated
    level class, drift z / loss-trend / norm values, episode counter."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="ModelHealth", level="warn", driftScore=5.26,
        lossTrend=0.31, weightNorm=122.6, updateNorm=3.14, gradNorm=4400.0,
        mse=[100.0, 110.0, 130.0], tenants=[], episodes=2,
    ))
    assert h.el("modelLevel").text == "warn"
    assert "warn" in h.el("modelLevel").class_set
    assert "ok" not in h.el("modelLevel").class_set
    assert h.el("driftScore").text == "5.3"
    assert h.el("lossTrend").text == "+31%"
    assert h.el("weightNorm").text == "122.6"
    assert h.el("updateNorm").text == "3.14"
    assert h.el("driftEpisodes").text == "2"
    # recovery flips the badge class back to ok
    h.ws.server_message(frame(
        jsonClass="ModelHealth", level="ok", driftScore=0.4, lossTrend=-0.02,
        weightNorm=123.0, updateNorm=1.0, gradNorm=4000.0, mse=[100.0],
        tenants=[], episodes=2,
    ))
    assert h.el("modelLevel").text == "ok"
    assert "ok" in h.el("modelLevel").class_set
    assert "warn" not in h.el("modelLevel").class_set
    assert h.el("lossTrend").text == "-2%"


def test_model_health_tenant_tiles_highlight_unhealthy():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="ModelHealth", level="alert", driftScore=9.5, lossTrend=0.0,
        weightNorm=10.0, updateNorm=1.0, gradNorm=100.0, mse=[1.0],
        tenants=[{"tenant": 0, "level": "ok", "drift": 0.3},
                 {"tenant": 1, "level": "alert", "drift": 9.5}],
        episodes=1,
    ))
    tiles = h.el("modelTenantsPanel").children
    assert len(tiles) == 2
    labels = [t.children[0].text for t in tiles]
    values = [t.children[1].text for t in tiles]
    assert labels == ["tenant 0", "tenant 1"]
    assert values == ["ok · z 0.3", "alert · z 9.5"]
    assert "alerting" in tiles[1].class_set
    assert "alerting" not in tiles[0].class_set
    # a healthy frame clears the tiles' highlight
    h.ws.server_message(frame(
        jsonClass="ModelHealth", level="ok", driftScore=0.2, lossTrend=0.0,
        weightNorm=10.0, updateNorm=1.0, gradNorm=100.0, mse=[1.0],
        tenants=[{"tenant": 0, "level": "ok", "drift": 0.2}], episodes=1,
    ))
    tiles = h.el("modelTenantsPanel").children
    assert all("alerting" not in t.class_set for t in tiles)


def test_model_health_loss_sparkline_draws():
    h = dashboard()
    h.ws.server_open()
    ctx = h.el("lossSpark").ctx
    ctx.calls.clear()
    h.ws.server_message(frame(
        jsonClass="ModelHealth", level="ok", driftScore=0.0, lossTrend=0.0,
        weightNorm=1.0, updateNorm=1.0, gradNorm=1.0,
        mse=[100.0, 120.0, 90.0, 130.0], tenants=[], episodes=0,
    ))
    assert len(ctx.ops("stroke")) == 1
    assert len(ctx.ops("lineTo")) == 3  # 4 points: 1 moveTo + 3 lineTo
    texts = [args[0] for op, args in ctx.ops("fillText")]
    assert any("130" in t for t in texts)  # last mse labeled
    # an empty window renders the placeholder, never throws
    ctx.calls.clear()
    h.ws.server_message(frame(
        jsonClass="ModelHealth", level="ok", driftScore=0.0, lossTrend=0.0,
        weightNorm=1.0, updateNorm=1.0, gradNorm=1.0, mse=[], tenants=[],
        episodes=0,
    ))
    assert len(ctx.ops("stroke")) == 0
    texts = [args[0] for op, args in ctx.ops("fillText")]
    assert any("waiting" in t for t in texts)


def test_model_health_empty_view_is_placeholder():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="ModelHealth", level="ok", driftScore=0.0, lossTrend=0.0,
        weightNorm=0.0, updateNorm=0.0, gradNorm=0.0, mse=[], tenants=[],
        episodes=0,
    ))
    assert h.el("modelTenantsPanel").children == []


def test_tenants_empty_view_is_placeholder():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(jsonClass="Tenants", tenants=[], gating=-1,
                              active=0))
    assert h.el("tenantsActive").text == "—"
    assert h.el("tenantsPanel").children == []


def test_metrics_backfill_fetched_on_boot():
    h = dashboard()
    urls = [u for u, _ in h.fetches]
    assert "/api/metrics" in urls
    assert "/api/hosts" in urls
    assert "/api/tenants" in urls
    assert "/api/model" in urls
    assert "/api/serving" in urls
    assert "/api/fleet" in urls


# ---------------------------------------------------------------------------
# serving plane tiles (ISSUE 9, mirrors the Hosts/Tenants suites)

def test_serving_frame_updates_tiles_and_level_badge():
    """Serving tiles: QPS/latency numbers, the active snapshot id, the
    snapshot-health badge class, and the error highlight."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Serving", qps=512.46, rowsPerSec=8200.0, p50Ms=8.24,
        p95Ms=61.0, p99Ms=84.06, snapshotStep=640, level="warn",
        requests=10000, rows=160000, errors=0, tenants=[],
    ))
    assert h.el("serveQps").text == "512.5"
    assert h.el("serveRows").text == "8,200"
    assert h.el("serveP50").text == "8.2"
    assert h.el("serveP99").text == "84.1"
    assert h.el("serveSnapshot").text == "ckpt-640"
    assert h.el("serveLevel").text == "warn"
    assert "warn" in h.el("serveLevel").class_set
    assert "ok" not in h.el("serveLevel").class_set
    assert h.el("serveErrors").text == "0"
    assert "degraded" not in h.el("serveErrors").class_set


def test_serving_frame_errors_highlight_and_tenant_tiles():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Serving", qps=10.0, rowsPerSec=160.0, p50Ms=5.0,
        p95Ms=9.0, p99Ms=12.0, snapshotStep=8, level="ok",
        requests=50, rows=800, errors=3,
        tenants=[{"tenant": 0, "rows": 500}, {"tenant": 1, "rows": 300}],
    ))
    assert "ok" in h.el("serveLevel").class_set
    assert h.el("serveErrors").text == "3"
    assert "degraded" in h.el("serveErrors").class_set
    tiles = h.el("servingTenantsPanel").children
    assert len(tiles) == 2
    assert tiles[0].children[0].text == "tenant 0"
    assert tiles[0].children[1].text == "500 rows"
    assert tiles[1].children[1].text == "300 rows"


def test_serving_empty_view_is_placeholder():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Serving", qps=0.0, rowsPerSec=0.0, p50Ms=0.0, p95Ms=0.0,
        p99Ms=0.0, snapshotStep=-1, level="", requests=0, rows=0, errors=0,
        tenants=[],
    ))
    assert h.el("serveQps").text == "—"
    assert h.el("serveSnapshot").text == "—"
    assert h.el("serveLevel").text == "—"
    assert h.el("servingTenantsPanel").children == []


# ---------------------------------------------------------------------------
# read-fleet tiles (ISSUE 11, mirrors the Serving suite)

def test_fleet_frame_updates_tiles_and_replica_row():
    """Fleet tiles: policy/requests/retries/ejections/champion numbers and
    one tile per replica, an ejected replica highlighted."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Fleet", policy="p99", requests=1234, retries=3,
        ejections=1, champion=2, replicas=[
            {"replica": 0, "url": "http://r0:8888", "healthy": True,
             "p99Ms": 84.4, "qps": 52.61, "requests": 700, "errors": 0,
             "ejections": 0, "snapshotStep": 640},
            {"replica": 1, "url": "http://r1:8888", "healthy": False,
             "p99Ms": 0.0, "qps": 0.0, "requests": 534, "errors": 4,
             "ejections": 1, "snapshotStep": 640},
        ],
    ))
    assert h.el("fleetPolicy").text == "p99"
    assert h.el("fleetRequests").text == "1,234"
    assert h.el("fleetRetries").text == "3"
    assert "degraded" in h.el("fleetRetries").class_set
    assert h.el("fleetEjections").text == "1"
    assert "degraded" in h.el("fleetEjections").class_set
    assert h.el("fleetChampion").text == "tenant 2"
    tiles = h.el("fleetPanel").children
    assert len(tiles) == 2
    assert tiles[0].children[0].text == "replica 0"
    assert tiles[0].children[1].text == "52.6 qps · p99 84 ms"
    assert "ejected" not in tiles[0].class_set
    assert tiles[1].children[0].text == "replica 1 · ejected"
    assert "ejected" in tiles[1].class_set


def test_fleet_empty_view_is_placeholder():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Fleet", policy="", replicas=[], requests=0, retries=0,
        ejections=0, champion=-1,
    ))
    assert h.el("fleetPolicy").text == "—"
    assert h.el("fleetChampion").text == "—"
    assert h.el("fleetRetries").text == "0"
    assert "degraded" not in h.el("fleetRetries").class_set
    assert h.el("fleetPanel").children == []


# ---------------------------------------------------------------------------
# freshness plane tiles (ISSUE 16, mirrors the Serving suite)

def test_freshness_frame_updates_tiles_and_sparkline():
    """Freshness tiles: event-lag percentiles, publish lag, watermark lag,
    the dominant critical-path edge, breach highlight, and the watermark
    sparkline drawn from the rolling window."""
    h = dashboard()
    h.ws.server_open()
    ctx = h.el("freshSpark").ctx
    ctx.calls.clear()
    h.ws.server_message(frame(
        jsonClass="Freshness", batches=42, rows=84000, eventLagMs=812.0,
        eventLagP50Ms=640.4, eventLagP95Ms=812.6, eventLagP99Ms=1500.0,
        publishLagP95Ms=990.0, watermarkLagMs=870.0,
        watermark=[800.0, 850.0, 870.0], critical="dispatch",
        criticalTicks={"dispatch": 30, "parse": 12}, sloMs=0.0,
        breachRun=0, breaches=2,
    ))
    assert h.el("freshP50").text == "640"
    assert h.el("freshP95").text == "813"
    assert h.el("freshP99").text == "1500"
    assert h.el("freshPublish").text == "990"
    assert h.el("freshWatermark").text == "870"
    assert h.el("freshCritical").text == "dispatch"
    assert h.el("freshBreaches").text == "2"
    assert "degraded" in h.el("freshBreaches").class_set
    assert len(ctx.ops("stroke")) == 1
    assert len(ctx.ops("lineTo")) == 2  # 3 points: 1 moveTo + 2 lineTo
    texts = [args[0] for op, args in ctx.ops("fillText")]
    assert any("870" in t for t in texts)  # last watermark lag labeled
    # a breach-free frame clears the highlight
    h.ws.server_message(frame(
        jsonClass="Freshness", batches=43, rows=86000, eventLagMs=700.0,
        eventLagP50Ms=640.0, eventLagP95Ms=810.0, eventLagP99Ms=1400.0,
        publishLagP95Ms=980.0, watermarkLagMs=860.0, watermark=[860.0],
        critical="parse", criticalTicks={"parse": 13}, sloMs=0.0,
        breachRun=0, breaches=0,
    ))
    assert h.el("freshCritical").text == "parse"
    assert "degraded" not in h.el("freshBreaches").class_set


def test_freshness_empty_view_is_placeholder():
    h = dashboard()
    h.ws.server_open()
    ctx = h.el("freshSpark").ctx
    ctx.calls.clear()
    h.ws.server_message(frame(
        jsonClass="Freshness", batches=0, rows=0, eventLagMs=-1.0,
        eventLagP50Ms=-1.0, eventLagP95Ms=-1.0, eventLagP99Ms=-1.0,
        publishLagP95Ms=-1.0, watermarkLagMs=-1.0, watermark=[],
        critical="", criticalTicks={}, sloMs=0.0, breachRun=0, breaches=0,
    ))
    assert h.el("freshP95").text == "—"
    assert h.el("freshWatermark").text == "—"
    assert h.el("freshCritical").text == "—"
    assert h.el("freshBreaches").text == "0"
    assert len(ctx.ops("stroke")) == 0
    texts = [args[0] for op, args in ctx.ops("fillText")]
    assert any("waiting" in t for t in texts)


def test_serving_frame_updates_snapshot_age_tile():
    """ISSUE 16 serving staleness: snapshotAgeS renders next to the
    snapshot id; a frame without it (legacy sender) shows the placeholder."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Serving", qps=10.0, rowsPerSec=160.0, p50Ms=5.0,
        p95Ms=9.0, p99Ms=12.0, snapshotAgeS=37.4, snapshotStep=8,
        level="ok", requests=50, rows=800, errors=0, tenants=[],
    ))
    assert h.el("serveAge").text == "37"
    # no snapshot yet → placeholder regardless of the age field
    h.ws.server_message(frame(
        jsonClass="Serving", qps=0.0, rowsPerSec=0.0, p50Ms=0.0, p95Ms=0.0,
        p99Ms=0.0, snapshotAgeS=-1.0, snapshotStep=-1, level="",
        requests=0, rows=0, errors=0, tenants=[],
    ))
    assert h.el("serveAge").text == "—"


def test_metrics_frame_updates_ingest_lag_and_rss_slope_tiles():
    """ISSUE 16 satellites: the sampled ingest event-time lag (ms → s) and
    the continuous RSS-slope gauge render on the pipeline panel; a frame
    without the lag gauge keeps the placeholder."""
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Metrics", counters={},
        gauges={"ingest.event_time_lag_ms": 2500.0,
                "host.rss_slope_mb_per_min": 1.257},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("ingestLag").text == "2.5"
    assert h.el("rssSlope").text == "1.26"
    h.ws.server_message(frame(
        jsonClass="Metrics", counters={}, gauges={},
        health={"phase": "healthy", "rtt_ms": 70.0, "transitions": 0},
    ))
    assert h.el("ingestLag").text == "—"
    assert h.el("rssSlope").text == "0.00"


def test_freshness_backfill_fetched_on_boot():
    h = dashboard()
    urls = [u for u, _ in h.fetches]
    assert "/api/freshness" in urls


# ---------------------------------------------------------------------------
# telemetry-historian tiles (ISSUE 20, mirrors the Freshness suite)


def test_history_frame_updates_tiles_and_sparklines():
    """History tiles: sample count, phase (with degraded highlight), RSS +
    slope, fetch RTT, disk footprint, perfGuard regression count (with
    highlight), and the three long-horizon sparklines."""
    h = dashboard()
    h.ws.server_open()
    ctx = h.el("histRssSpark").ctx
    ctx.calls.clear()
    h.ws.server_message(frame(
        jsonClass="History", samples=42, runId=7, phase="degraded",
        rssMb=512.4, rssSlopeMbPerMin=1.257, rttMs=71.3, diskMb=3.5,
        regressions=2, rss=[500.0, 506.0, 512.4], rtt=[70.0, 72.0, 71.3],
        stageMs=[4.0, 4.5, 5.1],
    ))
    assert h.el("histSamples").text == "42"
    assert h.el("histPhase").text == "degraded"
    assert "degraded" in h.el("histPhase").class_set
    assert h.el("histRss").text == "512"
    assert h.el("histSlope").text == "1.26"
    assert h.el("histRtt").text == "71.3"
    assert h.el("histDisk").text == "3.5"
    assert h.el("histRegressions").text == "2"
    assert "degraded" in h.el("histRegressions").class_set
    assert len(ctx.ops("stroke")) == 1
    assert len(ctx.ops("lineTo")) == 2  # 3 points: 1 moveTo + 2 lineTo
    texts = [args[0] for op, args in ctx.ops("fillText")]
    assert any("512.4" in t for t in texts)  # last RSS value labeled
    # a healthy, regression-free frame clears both highlights
    h.ws.server_message(frame(
        jsonClass="History", samples=43, runId=7, phase="healthy",
        rssMb=512.0, rssSlopeMbPerMin=0.01, rttMs=70.0, diskMb=3.5,
        regressions=0, rss=[512.0], rtt=[70.0], stageMs=[4.0],
    ))
    assert "degraded" not in h.el("histPhase").class_set
    assert "degraded" not in h.el("histRegressions").class_set


def test_history_empty_view_is_placeholder():
    h = dashboard()
    h.ws.server_open()
    ctx = h.el("histRssSpark").ctx
    ctx.calls.clear()
    h.ws.server_message(frame(
        jsonClass="History", samples=0, runId=0, phase="", rssMb=0.0,
        rssSlopeMbPerMin=0.0, rttMs=0.0, diskMb=0.0, regressions=0,
        rss=[], rtt=[], stageMs=[],
    ))
    assert h.el("histSamples").text == "—"
    assert h.el("histRss").text == "—"
    assert h.el("histPhase").text == "—"
    assert h.el("histRegressions").text == "0"
    assert len(ctx.ops("stroke")) == 0
    texts = [args[0] for op, args in ctx.ops("fillText")]
    assert any("waiting" in t for t in texts)


def test_history_backfill_fetched_on_boot():
    h = dashboard()
    urls = [u for u, _ in h.fetches]
    assert "/api/history" in urls


def test_unknown_jsonclass_is_ignored():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message(frame(jsonClass="Mystery", whatever=1))
    assert h.el("count").text == "0" or h.el("count").text == ""


def test_series_frames_drive_the_chart():
    h = dashboard()
    h.ws.server_open()
    ctx = h.el("livechart").ctx
    ctx.calls.clear()
    h.ws.server_message(frame(
        jsonClass="Series", real=[100, 200, 300], pred=[110, 190, 310],
        realStddev=15, predStddev=25,
    ))
    # 4 series drawn: real, pred, and both stdev bands
    assert len(ctx.ops("stroke")) == 4
    assert len(ctx.ops("lineTo")) > 0
    # legend labels drawn
    texts = [args[0] for op, args in ctx.ops("fillText")]
    for label in ("real", "predicted", "stdev real", "stdev pred"):
        assert label in texts


def test_live_series_buffer_until_backfill_lands():
    """Ordering contract (js/index.js:55-66): live Series frames arriving
    while the history fetch is in flight are buffered and applied AFTER the
    backfill, so the chart is chronological."""
    h, deferred = dashboard(defer_series=True)
    h.ws.server_open()
    # live frame arrives BEFORE the backfill response
    h.ws.server_message(frame(
        jsonClass="Series", real=[999], pred=[998], realStddev=1, predStddev=1,
    ))
    ctx = h.el("livechart").ctx
    ctx.calls.clear()
    # backfill resolves with history; then the pending live frame flushes
    deferred.resolve([
        {"jsonClass": "Series", "real": [1, 2], "pred": [1, 2],
         "realStddev": 0, "predStddev": 0},
    ])
    # chart drew at least twice (backfill push + flushed live push)
    assert len(ctx.ops("clearRect")) >= 2
    # a later live frame now applies immediately
    ctx.calls.clear()
    h.ws.server_message(frame(
        jsonClass="Series", real=[5], pred=[6], realStddev=0, predStddev=0,
    ))
    assert len(ctx.ops("clearRect")) == 1


def test_post_rides_websocket_when_open_else_http():
    h = dashboard()
    h.ws.server_open()
    h.interp.run("api.postStats(1, 2, 3, 4, 5);")
    h.interp.run_jobs()
    assert len(h.ws.sent) == 1
    sent = json.loads(h.ws.sent[0])
    assert sent == {"jsonClass": "Stats", "count": 1, "batch": 2, "mse": 3,
                    "realStddev": 4, "predStddev": 5}
    # close the socket: posts fall back to HTTP (reference api.js:65-79)
    h.fetch_routes["/api"] = {"status": "OK"}
    h.ws.server_close()
    before = len(h.fetches)
    h.interp.run("api.postConfig('id-1', 'http://h', ['7']);")
    h.interp.run_jobs()
    url, opts = h.fetches[before]
    assert url == "/api"
    assert opts.get("method") == "POST"
    assert json.loads(opts.get("body")) == {
        "jsonClass": "Config", "id": "id-1", "host": "http://h", "viz": ["7"],
    }


def test_reconnect_after_close_via_timer():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_close()
    assert len(h.timers) == 1  # the 5s reconnect
    h.run_timers()
    assert len(h.websockets) == 2  # a fresh socket was opened


def test_websocket_off_suppresses_reconnect():
    h = dashboard()
    h.ws.server_open()
    h.interp.run("api.websocketOff();")
    h.interp.run_jobs()
    assert not h.timers  # deliberate close: no reconnect scheduled


def test_guid_shape():
    h = dashboard()
    h.interp.run("window._g = api.guid();")
    guid = h.interp.global_this.get("_g")
    import re

    assert re.fullmatch(
        r"[0-9a-f]{8}-[0-9a-f]{4}-4[0-9a-f]{3}-[89ab][0-9a-f]{3}-[0-9a-f]{12}",
        guid,
    ), guid


def test_bad_frame_does_not_kill_the_dispatcher():
    h = dashboard()
    h.ws.server_open()
    h.ws.server_message("this is not json")
    h.ws.server_message(frame(
        jsonClass="Stats", count=7, batch=7, mse=7, realStddev=7, predStddev=7,
    ))
    assert h.el("count").text == "7"
    assert any("error" in line for line in h.console)


# ---------------------------------------------------------------------------
# negative controls: the suite's sensitivity is itself tested — a broken
# dispatch or a missing counter id must change observable behavior, so the
# assertions above would fail on a real regression

def test_negative_control_broken_dispatch_is_detected(tmp_path):
    """A typo'd jsonClass case in index.js leaves the counters un-updated —
    exactly what test_stats_frame_updates_all_five_counters asserts on."""
    with open(js_path("index.js"), encoding="utf-8") as fh:
        src = fh.read()
    broken = src.replace('case "Stats":', 'case "Statz":')
    assert broken != src, "mutation site vanished; update the control"
    mutated = tmp_path / "index.js"
    mutated.write_text(broken, encoding="utf-8")

    h = Harness([os.path.join(ASSETS, "index.html")])
    h.fetch_routes["/api/stats"] = {"jsonClass": "Stats", "count": 0, "batch": 0,
                                    "mse": 0, "realStddev": 0, "predStddev": 0}
    h.fetch_routes["/api/series"] = []
    h.load_script(js_path("api.js"))
    h.load_script(js_path("chart.js"))
    h.load_script(str(mutated))
    h.dom_content_loaded()
    h.ws.server_open()
    h.ws.server_message(frame(
        jsonClass="Stats", count=42, batch=1, mse=1, realStddev=1, predStddev=1,
    ))
    assert h.el("count").text != "42"  # the regression IS observable


def test_negative_control_missing_counter_id_is_detected():
    """Removing a counter element (as a renamed id in index.html would)
    makes the Stats handler throw — the dispatcher logs it and the counter
    never updates, so the positive tests would fail."""
    h = dashboard()
    h.ws.server_open()
    del h.elements["mse"]  # simulate id="mse" missing from index.html
    h.ws.server_message(frame(
        jsonClass="Stats", count=42, batch=1, mse=7, realStddev=9, predStddev=9,
    ))
    # the handler throws at the missing element: counters after it in the
    # update order never change — test_stats_frame_updates_all_five_counters
    # would fail on exactly this
    assert h.el("realStddev").text != "9"
    assert h.el("predStddev").text != "9"
    assert any("error" in line for line in h.console)


def test_negative_control_syntax_error_is_detected(tmp_path):
    """The lint catches a syntax break (the sbt-jshint analog)."""
    with open(js_path("api.js"), encoding="utf-8") as fh:
        src = fh.read()
    mutated = tmp_path / "api.js"
    mutated.write_text(src.replace("this.ws.send(text);",
                                   "this.ws.send(text"), encoding="utf-8")
    with pytest.raises(Exception):
        with open(mutated, encoding="utf-8") as fh:
            parse(fh.read())


# ---------------------------------------------------------------------------
# manual test harness page (test.html + api.js + test.js)

def harness_page():
    h = Harness([os.path.join(ASSETS, "test.html")])
    h.fetch_routes["/api"] = {"status": "OK"}
    for name in ("api.js", "test.js"):
        h.load_script(js_path(name))
    h.dom_content_loaded()
    return h


def test_harness_ws_toggle_and_log():
    h = harness_page()
    assert not h.websockets
    h.click("wsToggle")
    assert len(h.websockets) == 1
    assert h.el("wsToggle").text == "websocket: on"
    h.ws.server_open()
    h.ws.server_message(frame(jsonClass="Stats", count=1, batch=1, mse=1,
                              realStddev=1, predStddev=1))
    # the received frame was logged into the table (time cell + json cell);
    # rows also hold the _Socket open event — find the Stats row
    log_rows = h.el("log").rows
    assert log_rows, "no rows logged"
    assert any(
        len(r.rows) >= 2 and "Stats" in r.rows[1].text for r in log_rows
    ), [r.rows[1].text for r in log_rows if len(r.rows) >= 2]
    h.click("wsToggle")
    assert h.el("wsToggle").text == "websocket: off"


def test_harness_post_config_reads_form_fields():
    h = harness_page()
    h.el("cfgId").set("value", "abc")
    h.el("cfgHost").set("value", "http://lgn")
    h.el("cfgViz").set("value", " 1, 2 ,3")
    h.click("postConfig")
    url, opts = h.fetches[-1]
    assert url == "/api"
    body = json.loads(opts.get("body"))
    assert body == {"jsonClass": "Config", "id": "abc", "host": "http://lgn",
                    "viz": ["1", "2", "3"]}  # split(",").map(trim)


def test_harness_post_stats_numbers():
    h = harness_page()
    for el_id, value in (("stCount", "10"), ("stBatch", "2"), ("stMse", "30"),
                         ("stReal", "4"), ("stPred", "5")):
        h.el(el_id).set("value", value)
    h.click("postStats")
    body = json.loads(h.fetches[-1][1].get("body"))
    assert body == {"jsonClass": "Stats", "count": 10, "batch": 2, "mse": 30,
                    "realStddev": 4, "predStddev": 5}


# ---------------------------------------------------------------------------
# dashboard snapshot artifact (doc/dashboard.svg, VERDICT r3 #8)

def test_dashboard_snapshot_tool_produces_svg(tmp_path):
    """tools/dashboard_snapshot.py: the doc artifact is the real assets
    executing over a real training run — the SVG must carry the 4 chart
    series (chart.js's stroke colors) and non-zero counter values."""
    from tools import dashboard_snapshot as snap

    out = str(tmp_path / "dash.svg")
    snap.main(["--out", out])
    svg = open(out, encoding="utf-8").read()
    for color in ("rgb(30, 144, 255)", "rgb(255, 215, 0)",
                  "rgba(173, 216, 230, 0.5)", "rgba(238, 232, 170, 0.5)"):
        assert f'stroke="{color}"' in svg  # all 4 series drawn
    assert "polyline" in svg and "TWEETS TOTAL" in svg
    assert ">live<" in svg  # websocket badge reflected
    assert ">0</text>" not in svg.split("TWEETS TOTAL")[1].split("</g>")[0]
