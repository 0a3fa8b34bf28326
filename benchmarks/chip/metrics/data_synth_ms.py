"""Host time in the learner's calls to its dataset's client_batches per
server update (ms): the harness's span around host data synthesis."""


def read(w):
    if w.updates <= 0:
        return None
    return 1000.0 * w.spans.get("data.synth", 0.0) / w.updates
