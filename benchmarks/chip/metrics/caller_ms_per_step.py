"""Region + data bridge: milliseconds per step that the callers spend
inside their ``region(...)`` calls (input bridge, submit) and their
``result()`` calls (output bridge), summed over the callers; the
benchmark's own host-clock spans around each call."""


def read(rec):
    if not rec["steps"]:
        return None
    return sum(c for _, _, c in rec["steps"]) / len(rec["steps"]) * 1e3
