"""Least time of the decode attention served in the traced window (each
decode token's query against its live context, counted by the
configuration's architecture) over the device time of the Pallas kernel in
the decode program."""
from benchlib.readers import roofline_percent, trace_progress

PROGRAM = "jit__decode_paged_fn"


def read(run):
    if run.trace is None:
        return None
    flops, nbytes = run.work.decode_attention_work(trace_progress(run))
    return roofline_percent(run, flops, nbytes, PROGRAM)
