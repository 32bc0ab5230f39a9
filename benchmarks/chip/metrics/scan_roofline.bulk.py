"""Scan roofline share (bulk cells): the probed lists' real rows at the
chip's HBM peak over the scan stage's device time."""
from chipbench.readings import scan_roofline as read  # noqa: F401
