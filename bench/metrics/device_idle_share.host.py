"""Device: share of the traced span in which no operation ran while the
innermost open ``serving.*`` annotation was not ``serving.wait``, so the
scheduler's host work held the device up; at most ``device_idle_share``,
whose rest is the harness's loop (trace)."""
from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    share = spans.host_idle_share(ctx.trace, spans.serving_events(ctx))
    return None if share is None else 100.0 * share
