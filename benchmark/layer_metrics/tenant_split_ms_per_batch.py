"""Host time the tenant plane's routing took per batch: the ``tenant_split``
spans of the program's span file (route key, the M-way split and the stack
or pack of the tenant wire, ``parallel/tenants.TenantStackModel.prepare_wire``
on the scheduler's thread, inside ``wire_pack``) over their number. A
program without the span (a single-model cell, or a program from before
PR 35) gives None."""


def read(art):
    st = (art.get("spans") or {}).get("tenant_split")
    return None if not st or not st["count"] else st["total_ms"] / st["count"]
