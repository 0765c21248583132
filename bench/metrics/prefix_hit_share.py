"""Page cache: prompt tokens served from a cached prefix, as a share of the
prompt tokens admitted in the window (counters)."""


def read(ctx):
    if not ctx.prompt_tokens_admitted:
        return None
    return 100.0 * ctx.counters["cached_tokens"] / ctx.prompt_tokens_admitted
