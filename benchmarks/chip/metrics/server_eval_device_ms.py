"""Device time of aggregation, the FedAdam server step and the eval
program per server update (ms), from the trace's program events
(fedbench/layers.json)."""


def read(w):
    if w.reduced is None or w.updates <= 0:
        return None
    s = w.reduced.layer_s.get("server_eval")
    return None if s is None else 1000.0 * s / w.updates
