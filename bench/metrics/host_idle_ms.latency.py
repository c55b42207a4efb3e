"""Device idle in the traced window that lies under the engine's other
``engine.*`` spans (host work: admission, chunk prep, page-table updates,
dispatch, bookkeeping, the wait between ticks) and not under
``engine.sync``, per engine tick in the trace (``benchlib.spans``)."""
from benchlib.spans import idle_ms_per_tick


def read(run):
    return idle_ms_per_tick(run, "host")
