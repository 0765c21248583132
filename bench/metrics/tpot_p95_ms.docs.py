"""Per-token time of a cell above its knee: 95th percentile (nearest
rank) over requests with two or more tokens in the window of (last token
- first token) / (tokens - 1). Per layer, where the seed's order of the
same requests moves it too far for a bound (PERF.md); end to end in the
cells where it holds still."""


def read(ctx):
    return ctx.summary["tpot_p95_ms"]
