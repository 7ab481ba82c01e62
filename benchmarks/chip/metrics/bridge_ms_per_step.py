"""Data bridge: milliseconds per step in the program's spans
``region.bridge_in`` (the input bridge program and the rows' reshape to
f32) and ``region.bridge_out`` (the output bridge program), summed over
the callers (program tracer, host clock)."""
import program_spans

NAMES = ("region.bridge_in", "region.bridge_out")


def read(rec):
    return program_spans.ms_per_step(rec, NAMES)
