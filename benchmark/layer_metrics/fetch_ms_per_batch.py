"""Mean duration of the program's ``fetch`` spans that started in the
window."""


def read(art):
    st = (art.get("spans") or {}).get("fetch")
    return None if not st else st["total_ms"] / st["count"]
