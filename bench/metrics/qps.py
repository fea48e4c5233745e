"""Queries completed in the window over the window's wall time."""


def read(w):
    return len(w.report.results) / w.wall_s
