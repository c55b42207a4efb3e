"""Device idle in the traced window that lies under the engine's
``engine.sync`` spans (its blocking device-to-host reads), per engine tick
in the trace (``benchlib.spans``)."""
from benchlib.spans import idle_ms_per_tick


def read(run):
    return idle_ms_per_tick(run, "sync")
