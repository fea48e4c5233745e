"""``idle_share`` under the live traffic (see ``bench/readers.py``)."""
from bench.readers import idle_share as read  # noqa: F401
