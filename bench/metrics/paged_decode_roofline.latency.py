"""Least time of the decode attention served in the traced window (each
decode token's query against its live context, ``benchlib.work``) over
the device time of the Pallas kernel in the decode program."""
from benchlib import work
from benchlib.readers import roofline_percent, trace_progress

PROGRAM = "jit__decode_paged_fn"


def read(run):
    if run.trace is None:
        return None
    flops, nbytes = work.decode_attention_work(run.shapes,
                                               trace_progress(run))
    return roofline_percent(run, flops, nbytes, PROGRAM)
