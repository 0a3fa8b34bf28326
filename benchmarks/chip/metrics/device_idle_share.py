"""Share of the traced window in which no operation ran on the device:
1 - union of op intervals / window, in percent."""


def read(w):
    if w.reduced is None or w.reduced.window_s <= 0:
        return None
    return 100.0 * (1.0 - w.reduced.busy_s / w.reduced.window_s)
