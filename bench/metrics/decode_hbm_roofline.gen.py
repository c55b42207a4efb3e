"""Least time of the decode calls in the traced window (every weight a
call reads, and per decode row its slot's recurrent state read and
written and its live KV read, counted by the configuration's
architecture) over the union of the device-op intervals of the decode
program in the window.  Calls are the ``engine.decode`` host spans that
start in the window; rows and contexts are the requests' progress over
it."""
from benchlib.readers import trace_progress
from benchlib.work import least_seconds

PROGRAM = "jit__decode_paged_fn"
DECODE = "engine.decode"


def _union_s(ops):
    total, end = 0.0, None
    for o in sorted(ops, key=lambda o: o.start):
        if end is None or o.start > end:
            total += o.end - o.start
            end = o.end
        elif o.end > end:
            total += o.end - end
            end = o.end
    return total * 1e-9


def read(run):
    count = getattr(run.work, "decode_step_bytes", None)
    if run.trace is None or count is None:
        return None
    w0 = run.trace.window[0]
    calls = sum(1 for name, s, _e in run.trace.host
                if name == DECODE and s > w0)
    spent = sum(_union_s([o for o in ops if o.module.startswith(PROGRAM)])
                for ops in run.trace.devices)
    if calls == 0 or spent <= 0.0:
        return None
    flops, nbytes = count(trace_progress(run), calls)
    least = least_seconds(flops, nbytes, run.peaks.bf16_flops,
                          run.peaks.hbm_bytes_per_s)
    return 100.0 * least / spent
