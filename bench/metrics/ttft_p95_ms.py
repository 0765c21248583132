"""The serving loop as a user meets it: 95th percentile (nearest rank) of
due time to first token over every request due in the window; a request
without a first token by the window's end counts with the wait it has had.
A per-layer metric, read in the traced run: between untraced runs it
spread too widely for any bound the benchmark may set (PERF.md)."""


def read(ctx):
    return ctx.summary["ttft_p95_ms"]
