"""Least time of the chunked SSD scans served in the traced window (each
engine chunk of each prompt through every Mamba-2 layer, counted by the
configuration's architecture) over the device time of the Pallas kernels
named ``ssd_scan`` in the prefill-chunk program.  The attention layer's
``flash_attention`` in the same program is not counted."""
from benchlib.readers import trace_progress
from benchlib.work import least_seconds

PROGRAM = "jit__chunk_paged_fn"
KERNEL = "ssd_scan"


def read(run):
    count = getattr(run.work, "ssd_prefill_work", None)
    if run.trace is None or count is None:
        return None
    spent = sum(o.end - o.start for ops in run.trace.devices for o in ops
                if o.kernel and o.module.startswith(PROGRAM)
                and o.name.startswith(KERNEL)) * 1e-9
    flops, nbytes = count(trace_progress(run), run.served.chunk_tokens)
    if spent <= 0.0 or (flops == 0 and nbytes == 0):
        return None
    least = least_seconds(flops, nbytes, run.peaks.bf16_flops,
                          run.peaks.hbm_bytes_per_s)
    return 100.0 * least / spent
