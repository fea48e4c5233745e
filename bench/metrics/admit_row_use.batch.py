"""``admit_row_use`` under the batch traffic (see ``bench/stages.py``)."""
from bench.stages import admit_row_use as read  # noqa: F401
