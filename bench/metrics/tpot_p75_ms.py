"""75th percentile, over the requests due in the window, of the time per
output token after the first: (finish - first token) / (tokens - 1), or,
for a request still decoding when the wait ends, the same over the tokens
it was seen to have produced (at least ``serve.TPOT_TOKENS``)."""
from benchlib.readers import counted, tail_ms


def read(run):
    vals = []
    for t in counted(run):
        r = t.req
        if r is None or r.error or r.first_token_at is None:
            vals.append(None)
        elif r.done and len(r.generated) > 1:
            vals.append((r.finished_at - r.first_token_at)
                        / (len(r.generated) - 1))
        elif not r.done and t.seen is not None and t.seen[0] > 1:
            vals.append((t.seen[1] - r.first_token_at) / (t.seen[0] - 1))
        elif not r.done:
            vals.append(None)
    return tail_ms(vals, 75)
