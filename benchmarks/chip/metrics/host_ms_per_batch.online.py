"""Host time per batch beyond the device search (online cells)."""
from chipbench.readings import host_ms_per_batch as read  # noqa: F401
