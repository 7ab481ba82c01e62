"""Region + data bridge: milliseconds per step in the program's spans
``region.call`` (a caller's region call, from entry until its handle is
returned) and ``region.result`` (a caller's ``result()``: the wait and
the output bridge), summed over the callers (program tracer, host
clock).  The program's own count of what ``caller_ms_per_step`` times
from outside."""
import program_spans

NAMES = ("region.call", "region.result")


def read(rec):
    return program_spans.ms_per_step(rec, NAMES)
