"""Real (unpadded) client tokens trained in the window per second.

Rows per client are min(samples, local steps x batch), from the
benchmark's own copy of the sample counts, for each contributor the
learner received in the window; tokens are rows x seq_len."""


def read(w):
    return w.tokens / w.window_s
