"""Seconds from the start of the benchmark's process to the start of the
window: imports, the device, weights and inputs made from the seed, the
bundle written and loaded, and the warm-up steps with their compiles or
compile-cache loads (host clock)."""


def read(rec):
    return rec["setup_s"]
