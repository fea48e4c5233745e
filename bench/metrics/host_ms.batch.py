"""``host_ms`` under the batch traffic (see ``bench/stages.py``)."""
from bench.stages import host_ms as read  # noqa: F401
