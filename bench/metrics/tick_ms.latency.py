"""The window over the engine's tick count in it: every tick ends in a
device-to-host sync, so this is a tick's wall time."""
from benchlib.readers import ticks, window_s


def read(run):
    n = ticks(run)
    return 1e3 * window_s(run) / n if n else None
