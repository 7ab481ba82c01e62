"""Queue + batcher: milliseconds per step in the program's spans
``batch.gather``, ``batch.to_host`` and ``batch.scatter`` (program
tracer, host clock)."""

NAMES = ("batch.gather", "batch.to_host", "batch.scatter")


def read(rec):
    spans = rec["spans"]
    if not spans or not rec["steps"]:
        return None
    total = [s.dur_s for s in spans if s.name in NAMES]
    if not total:
        return None
    return sum(total) / len(rec["steps"]) * 1e3
