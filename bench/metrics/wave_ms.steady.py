"""``wave_ms`` under the steady traffic (see ``bench/readers.py``)."""
from bench.readers import wave_ms as read  # noqa: F401
