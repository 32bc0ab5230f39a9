"""Search roofline share (bulk cells): bytes the search needs at 819 GB/s over
the search program's device time."""
from chipbench.readings import search_roofline as read  # noqa: F401
