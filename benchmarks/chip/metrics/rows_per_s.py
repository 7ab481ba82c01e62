"""Surrogate rows returned to the callers per second, over all the rows
and all the time of the window (host clock)."""


def read(rec):
    if rec["window_s"] <= 0:
        return None
    return rec["rows"] / rec["window_s"]
