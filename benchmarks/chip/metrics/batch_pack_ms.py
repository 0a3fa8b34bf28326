"""Host time padding and stacking client batches per server update (ms):
the program's span ``client.pack`` over the traced window (the harness's
``data.synth`` holds the batches' synthesis). None where the program
recorded no span."""


def read(w):
    spans = (getattr(w, "program", None) or {}).get("spans")
    if not spans or "client.pack" not in spans or w.updates <= 0:
        return None
    return 1000.0 * spans["client.pack"]["s"] / w.updates
