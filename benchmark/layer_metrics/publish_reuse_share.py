"""Share of the publisher's requests that went out on a connection the
client had KEPT from the one before: over the ``stats_publish`` spans of the
program's span file that carry ``posts`` (the requests that update sent:
``Stats`` and ``Series``, on every eighth update the metrics frames too) and
``connects`` (the connections it opened for them;
``telemetry/web_client.WebClient``, ``telemetry/session_stats.py``),
100 × (Σ ``posts`` − Σ ``connects``) ÷ Σ ``posts``. Against a server that
keeps connections (the dashboard, ``web/server.py``) it reads ~100; against
one that answers ``Connection: close`` and closes, as the harness's sink
does, every request connects and it reads 0. Read from the file itself as
``paired_delivery_share`` is (the two args are not in ``art["spans"]``), so
it is over every update of the window run. A program whose spans lack the
args gives None."""

from benchmark import spans, trace_files


def read(art):
    path = trace_files.span_file()
    if path is None:
        return None
    posts = connects = 0
    for ev in spans.load_events(path):
        a = ev.get("args") or {}
        if (ev.get("ph") == "X" and ev.get("name") == "stats_publish"
                and "posts" in a and "connects" in a):
            posts += int(a["posts"])
            connects += int(a["connects"])
    return 100.0 * (posts - connects) / posts if posts else None
