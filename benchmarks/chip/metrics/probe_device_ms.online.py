"""Device time of the search's probe stage per batch (online cells)."""
from chipbench.stages import device_ms

read = device_ms("probe")
