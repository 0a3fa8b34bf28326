"""Host time per server update (ms) outside every learner call and the
harness's own callback: the engine's planning, resolving and logging."""


def read(w):
    if w.updates <= 0:
        return None
    return 1000.0 * w.engine_s / w.updates
