"""``wait_ms`` under the batch traffic (see ``bench/stages.py``)."""
from bench.stages import wait_ms as read  # noqa: F401
