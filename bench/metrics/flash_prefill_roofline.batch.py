"""Least time of the chunked-prefill attention served in the traced
window (each chunk's causal attention over its prefix, ``benchlib.work``)
over the device time of the Pallas kernel in the prefill-chunk program."""
from benchlib import work
from benchlib.readers import roofline_percent, trace_progress

PROGRAM = "jit__chunk_paged_fn"


def read(run):
    if run.trace is None:
        return None
    flops, nbytes = work.prefill_attention_work(
        run.shapes, trace_progress(run), run.served.chunk_tokens)
    return roofline_percent(run, flops, nbytes, PROGRAM)
