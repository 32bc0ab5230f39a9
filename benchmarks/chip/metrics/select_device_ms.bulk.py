"""Device time of the search's select stage per batch (bulk cells)."""
from chipbench.stages import device_ms

read = device_ms("select")
