"""The publisher's schedule (telemetry/session_stats.py, PR 53): what the
session owes once every ``METRICS_EVERY`` updates goes out ONE ITEM AN
UPDATE, each at a phase of its own, after that update's ``Stats`` and
``Series``; ``publish_metrics()`` still sends all of it in one call.

No server: the client's ``_request`` is replaced by a recorder of each
request's ``jsonClass``, and the five views and the historian's hook by
stand-ins, so every item has something to send unless a case takes it away.
"""

from __future__ import annotations

import ast
import json

import pytest

from twtml_tpu.telemetry import (
    freshness, historian, modelwatch, sideband, tenants)
from twtml_tpu.telemetry import session_stats
from twtml_tpu.telemetry.session_stats import METRICS_EVERY, SessionStats

CLOSED = "http://127.0.0.1:9"

# each item's frame, by the phase it is sent on; the phases left are plain
FRAMES = ["Metrics", "Hosts", "Tenants", "ModelHealth", "Freshness",
          "History"]
VIEWS = {
    "Hosts": (sideband, "last_hosts", {
        "hosts": [], "straggler": -1, "stage": "", "skew_ms": 0.0}),
    "Tenants": (tenants, "last_tenants", {
        "tenants": [], "gating": -1, "active": 0}),
    "ModelHealth": (modelwatch, "last_model", {
        "level": "ok", "drift_score": 0.0, "loss_trend": 0.0,
        "weight_norm": 0.0, "update_norm": 0.0, "grad_norm": 0.0,
        "mse": [1.0], "tenants": [], "episodes": 0}),
    "Freshness": (freshness, "last_freshness", {"batches": 1}),
    "History": (historian, "last_history", {"samples": 1}),
}


class _Conf:
    lightning = CLOSED
    twtweb = CLOSED
    webTimeout = 0.2


@pytest.fixture()
def session(monkeypatch):
    """A session (not opened: no Lightning chart) whose requests land in
    ``session.sent`` and whose historian samples in ``session.samples``,
    with every view set."""
    s = SessionStats(_Conf())
    s.sent, s.samples = [], []

    def request(kind="", data=None):
        s.web.requests += 1
        s.sent.append(json.loads(data)["jsonClass"])
        return "{}"

    monkeypatch.setattr(s.web, "_request", request)
    monkeypatch.setattr(historian, "sample",
                        lambda: s.samples.append(s._updates))
    for module, name, view in VIEWS.values():
        monkeypatch.setattr(module, name, lambda view=view: dict(view))
    return s


def _updates(s, n):
    """``n`` updates: the kinds each one sent, in order."""
    real, per_update = [float(k) for k in range(8)], []
    for k in range(n):
        before = len(s.sent)
        s.update(8 * (k + 1), 8, 3.0, 1.0, 1.0, real, real)
        per_update.append(s.sent[before:])
    return per_update


def _phase(update):
    """The phase of the ``update``-th update (0-based) of a session."""
    return (update + 1) % METRICS_EVERY


def test_each_item_has_a_phase_of_its_own_and_two_phases_are_plain(session):
    per_update = _updates(session, 2 * METRICS_EVERY)
    extra = [kinds[2:] for kinds in per_update]
    assert extra == [
        [FRAMES[_phase(u)]] if _phase(u) < len(FRAMES) else []
        for u in range(2 * METRICS_EVERY)]
    assert len(FRAMES) == 6 and sum(not e for e in extra) == 2 * 2
    # the period of every frame, and of the sample, is METRICS_EVERY
    for kind in FRAMES:
        assert session.sent.count(kind) == 2
    assert session.samples == [METRICS_EVERY, 2 * METRICS_EVERY]


def test_stats_then_series_open_every_update_on_every_phase(session):
    per_update = _updates(session, METRICS_EVERY)
    assert [kinds[:2] for kinds in per_update] == [
        ["Stats", "Series"]] * METRICS_EVERY
    assert max(len(kinds) for kinds in per_update) == 3


def test_publish_metrics_sends_every_frame_in_one_call(session):
    session.publish_metrics()
    assert session.sent == FRAMES
    assert len(session.samples) == 1
    assert session._updates == 0  # the schedule's clock is not touched


@pytest.mark.parametrize("absent", sorted(VIEWS))
def test_a_view_that_is_none_costs_no_post_and_leaves_its_phase_plain(
        session, monkeypatch, absent):
    module, name, _ = VIEWS[absent]
    monkeypatch.setattr(module, name, lambda: None)
    per_update = _updates(session, METRICS_EVERY)
    for u, kinds in enumerate(per_update):
        due = FRAMES[_phase(u)] if _phase(u) < len(FRAMES) else None
        assert kinds[2:] == ([] if due in (None, absent) else [due])
    assert absent not in session.sent
    del session.sent[:]
    session.publish_metrics()
    assert session.sent == [k for k in FRAMES if k != absent]


def test_an_open_breaker_sends_nothing_and_the_historian_still_samples(
        session):
    for _ in range(session._web_breaker.failure_threshold):
        session._web_breaker.record_failure()
    assert session._web_breaker.state == session._web_breaker.OPEN
    assert _updates(session, 2 * METRICS_EVERY) == [[]] * (2 * METRICS_EVERY)
    assert session.samples == [METRICS_EVERY, 2 * METRICS_EVERY]
    session.publish_metrics()
    assert session.sent == [] and len(session.samples) == 3


def test_a_frame_that_fails_never_raises_and_feeds_the_breaker(
        session, monkeypatch):
    def down(view):
        raise OSError("dashboard gone")

    monkeypatch.setattr(session.web, "freshness", down)
    per_update = _updates(session, METRICS_EVERY)
    assert "Freshness" not in session.sent
    assert [k for kinds in per_update for k in kinds[2:]] == [
        k for k in FRAMES[1:] + FRAMES[:1] if k != "Freshness"]
    # a success after it closed the count again
    assert session._web_breaker._consecutive == 0


def test_the_historian_is_sampled_from_one_place_and_tw010_passes():
    """lawcheck TW010 on the tree, and what the rule cannot see: inside the
    seam file the hook is called ONCE (item 0), not once an item."""
    from tools.lawcheck import engine

    report = engine.run_repo()
    assert [f.render() for f in report.findings if f.rule == "TW010"] == []
    with open(session_stats.__file__, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute) and n.func.attr == "sample"]
    assert len(calls) == 1
    owner = [f.name for f in ast.walk(tree)
             if isinstance(f, ast.FunctionDef) and calls[0] in ast.walk(f)]
    assert owner == [SessionStats._PERIODIC[0].__name__]
