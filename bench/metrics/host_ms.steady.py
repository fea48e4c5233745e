"""``host_ms`` under the steady traffic (see ``bench/stages.py``)."""
from bench.stages import host_ms as read  # noqa: F401
