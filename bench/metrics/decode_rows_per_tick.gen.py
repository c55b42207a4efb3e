"""Output tokens produced in the window over the engine's ticks in it."""
from benchlib.readers import served_tokens, ticks


def read(run):
    n = ticks(run)
    return served_tokens(run) / n if n else None
