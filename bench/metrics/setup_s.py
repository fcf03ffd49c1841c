"""``setup_s``: seconds from process start to the start of the window
(loading, weights, payload pool, compiling and warming the buckets)."""


def read(run):
    return run.setup_s
