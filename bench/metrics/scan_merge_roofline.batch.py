"""``scan_merge_roofline`` under the batch traffic (see
``bench/readers.py``)."""
from bench.readers import scan_merge_roofline as read  # noqa: F401
