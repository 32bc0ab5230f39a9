"""Search program device time per batch (online cells)."""
from chipbench.readings import search_device_ms as read  # noqa: F401
