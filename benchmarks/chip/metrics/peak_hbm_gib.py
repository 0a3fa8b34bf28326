"""Peak device memory of the run, ``peak_bytes_in_use`` after the
window, in GiB."""


def read(w):
    return w.memory_peak_bytes / 2 ** 30 if w.memory_peak_bytes else None
