"""setup_s: Seconds from process start to the window's start: weights, engine, warm-up
and (in a cold checkout) compilation."""


def read(run):
    return run.setup_s
