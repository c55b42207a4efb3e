"""1 - (union of device-op intervals / traced window), from the trace."""
from benchlib.readers import idle_percent


def read(run):
    return idle_percent(run)
