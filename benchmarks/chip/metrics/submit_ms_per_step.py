"""Queue + batcher: milliseconds per step in the program's span
``queue.submit``, the whole of each caller's ``ServeQueue.submit``,
summed over the callers (program tracer, host clock)."""
import program_spans

NAMES = ("queue.submit",)


def read(rec):
    return program_spans.ms_per_step(rec, NAMES)
