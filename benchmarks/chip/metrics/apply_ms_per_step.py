"""Engine: milliseconds per step in the program's span ``batch.apply``,
which ends once the engine's output is ready (program tracer, host
clock).  Not ``engine.apply``: that span ends when the apply is
enqueued."""


def read(rec):
    spans = rec["spans"]
    if not spans or not rec["steps"]:
        return None
    total = [s.dur_s for s in spans if s.name == "batch.apply"]
    if not total:
        return None
    return sum(total) / len(rec["steps"]) * 1e3
