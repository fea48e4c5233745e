"""99th percentile of the wait from when a request was due to its
admission into a lane."""
from bench.readers import percentile


def read(w):
    return percentile(w.queue_ms(), 99)
