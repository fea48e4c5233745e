"""``wave_ms`` under the batch traffic (see ``bench/readers.py``)."""
from bench.readers import wave_ms as read  # noqa: F401
