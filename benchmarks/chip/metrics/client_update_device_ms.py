"""Device time of the client-update programs per server update (ms),
from the trace's program events (fedbench/layers.json)."""


def read(w):
    if w.reduced is None or w.updates <= 0:
        return None
    s = w.reduced.layer_s.get("client_update")
    return None if s is None else 1000.0 * s / w.updates
