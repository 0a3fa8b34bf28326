"""Host time in the program's host<->device copies per server update (ms):
the spans of ``repro.spans`` whose names end in ``to_device`` or
``to_host`` (client batches and mask, a stale base, the deltas down and
back up, the history copy), over the traced window. None where the
program recorded no span."""

COPIES = ("to_device", "to_host")


def read(w):
    spans = (getattr(w, "program", None) or {}).get("spans")
    if not spans or w.updates <= 0:
        return None
    s = sum(v["s"] for n, v in spans.items() if n.endswith(COPIES))
    return 1000.0 * s / w.updates
