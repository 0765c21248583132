"""Model step: device milliseconds of the prefill programs per 1,000 prompt
tokens they computed (cached tokens excluded), in the traced span."""
from bench import readers


def read(ctx):
    m = readers.matched(ctx, readers.PREFILL, "prefill")
    if m is None:
        return None
    ds, dev_s = m
    toks = sum(d.tokens for d in ds)
    return 1e3 * dev_s / (toks / 1e3) if toks else None
