"""The client update's share of the chip's bf16 peak while its programs
run (%): useful forward + backward FLOPs of the window's real tokens (as
``mfu`` counts them) over the device time of the programs named
``*client_update*`` (fedbench/layers.json) x chips x the device kind's
bf16 peak. ``mfu`` divides by the whole window instead; this divides by
the client programs' own time, so host stalls around them do not count.
None where the trace holds no client-update program."""


def read(w):
    peak = (w.peak or {}).get("bf16_flops_per_s")
    s = None if w.reduced is None else w.reduced.layer_s.get("client_update")
    if not peak or not s or w.useful_flops <= 0:
        return None
    return 100.0 * w.useful_flops / (s * w.chips * peak)
