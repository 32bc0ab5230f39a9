"""Queries answered in the window over the window's seconds."""


def read(rec):
    return rec["answered"] / rec["window_s"], "queries/s"
