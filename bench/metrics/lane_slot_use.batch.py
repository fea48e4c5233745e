"""Probes taken over the lane-probe slots the waves offered."""


def read(w):
    r = w.report
    return sum(r.probes.values()) / r.lane_steps if r.lane_steps else None
