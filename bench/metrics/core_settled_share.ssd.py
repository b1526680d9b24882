"""Core search: share of the SSD batches' row-rounds in which the row
had already settled, in %, from the ``core_rounds`` and
``core_row_rounds`` tags of the engine's phase spans: a batch runs until
its slowest row settles."""
from yardstick.phases import core_settled_share as read  # noqa: F401
