"""Whole-step model FLOP utilization: useful forward + backward FLOPs of
the window's client updates (real tokens x the configuration's FLOPs per
token) over window seconds x chips x the device kind's bf16 peak, in
percent. Padding steps and the second forward of a local step count
nothing."""


def read(w):
    peak = (w.peak or {}).get("bf16_flops_per_s")
    if not peak or w.window_s <= 0 or w.useful_flops <= 0:
        return None
    return 100.0 * w.useful_flops / (w.window_s * w.chips * peak)
