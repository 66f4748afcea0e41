"""Mean duration of the program's ``stats_publish`` spans that started in
the window (the POSTs to the dashboard; the harness's sink answers them)."""


def read(art):
    st = (art.get("spans") or {}).get("stats_publish")
    return None if not st else st["total_ms"] / st["count"]
