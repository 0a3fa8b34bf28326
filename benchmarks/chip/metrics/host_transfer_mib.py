"""Bytes the program copied between host and device per server update
(MiB): the ``bytes`` of the spans of ``repro.spans`` whose names end in
``to_device`` or ``to_host``, over the traced window; a count from the
arrays' shapes. None where the program recorded no span."""

COPIES = ("to_device", "to_host")


def read(w):
    spans = (getattr(w, "program", None) or {}).get("spans")
    if not spans or w.updates <= 0:
        return None
    b = sum(v["bytes"] for n, v in spans.items() if n.endswith(COPIES))
    return b / 2 ** 20 / w.updates
