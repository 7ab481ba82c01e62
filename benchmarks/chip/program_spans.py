"""Per-step sums of the program's own spans, for the metric readers that
read the program tracer's ring of a ``--trace 1`` run."""


def ms_per_step(rec, names):
    """Milliseconds per step in the program spans named ``names``, or
    ``None`` where the run recorded none of them: an untraced run, or a
    program that does not write them."""
    spans = rec["spans"]
    if not spans or not rec["steps"]:
        return None
    total = [s.dur_s for s in spans if s.name in names]
    if not total:
        return None
    return sum(total) / len(rec["steps"]) * 1e3
