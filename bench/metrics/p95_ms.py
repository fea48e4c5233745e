"""95th percentile, over every request of the window, of the time
from when the request was due to when ``serve`` returned its result."""
from bench.readers import percentile


def read(w):
    return percentile(w.latency_ms(), 95)
