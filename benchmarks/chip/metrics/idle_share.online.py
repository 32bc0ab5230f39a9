"""Device idle share of the traced window (online cells)."""
from chipbench.readings import idle_share as read  # noqa: F401
