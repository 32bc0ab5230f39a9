"""Device idle share of the traced window (bulk cells)."""
from chipbench.readings import idle_share as read  # noqa: F401
