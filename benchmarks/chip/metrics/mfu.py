"""Whole step: the FLOPs of the rows returned per second over the peak of
the chips the cell uses, in percent, over the traced window.  FLOPs per
row are the architecture's count (``flops_per_row`` of the run's
record)."""
import work


def read(rec):
    if rec["trace"] is None or rec["window_s"] <= 0:
        return None
    flops = rec["rows"] * rec["flops_per_row"]
    return 100.0 * flops / rec["window_s"] / (
        rec["chips"] * work.peak_for(rec["device_kind"])["flops_per_s"])
