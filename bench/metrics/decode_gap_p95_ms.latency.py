"""95th percentile of the gaps between consecutive tokens of a request
(``Request.token_times``), over every request's gaps whose later token
came inside the window (``benchlib.spans``)."""
from benchlib.spans import token_gap_ms


def read(run):
    return token_gap_ms(run, 95)
