"""Kernel: the roofline's least time over the device time of the
engine's apply program (the XLA module ``jit_apply_fn``, whatever kernel
runs inside it), in percent.  The least time of a call is the larger of
its FLOPs over the chip's peak FLOP/s and its bytes over the peak bytes/s
(``work.py``), from the architecture's counts at the rows the call served
on that chip, not the padded ones (``flops_per_row`` and ``call_bytes``
of the run's record)."""
import trace_reduce
import work

MODULE = "jit_apply_fn"


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    calls, secs = trace_reduce.module_time(tr, MODULE)
    if not calls or secs <= 0:
        return None
    rows = rec["rows_per_step"] // rec["chips"]
    peak = work.peak_for(rec["device_kind"])
    least = calls * work.least_time_s(rec["flops_per_row"] * rows,
                                      rec["call_bytes"], peak)
    return 100.0 * least / secs
