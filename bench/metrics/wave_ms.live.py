"""``wave_ms`` under the live traffic (see ``bench/readers.py``)."""
from bench.readers import wave_ms as read  # noqa: F401
