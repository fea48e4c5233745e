"""``admit_share`` under the steady traffic (see ``bench/readers.py``)."""
from bench.readers import admit_share as read  # noqa: F401
