"""Least time of the chunked-prefill attention served in the traced
window (each chunk's causal attention over its prefix, counted by the
configuration's architecture) over the device time of the Pallas kernel in
the prefill-chunk program."""
from benchlib.readers import roofline_percent, trace_progress

PROGRAM = "jit__chunk_paged_fn"


def read(run):
    if run.trace is None:
        return None
    flops, nbytes = run.work.prefill_attention_work(
        trace_progress(run), run.served.chunk_tokens)
    return roofline_percent(run, flops, nbytes, PROGRAM)
