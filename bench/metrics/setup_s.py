"""Seconds from process start to the first timed query: generation,
build_index, compilation (from the cache after a cell's first run) and
warm-up of the cell's own shapes."""


def read(w):
    return w.setup_s
