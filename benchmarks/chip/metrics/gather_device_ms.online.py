"""Device time of the search's gather stage per batch (online cells)."""
from chipbench.stages import device_ms

read = device_ms("gather")
