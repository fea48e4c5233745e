"""``wait_ms`` under the steady traffic (see ``bench/stages.py``)."""
from bench.stages import wait_ms as read  # noqa: F401
