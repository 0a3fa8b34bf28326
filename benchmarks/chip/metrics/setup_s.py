"""Seconds from process start to the first instant of the window:
imports, the schedule replay, warm-up (compiles or cache loads), the
learner's construction and the checked first server updates."""


def read(w):
    return w.setup_s
