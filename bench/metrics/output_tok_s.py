"""Output tokens produced inside the window (each request's generated
length at the window's two edges) over the window."""
from benchlib.readers import served_tokens, window_s


def read(run):
    n = served_tokens(run)
    return n / window_s(run) if n else None
