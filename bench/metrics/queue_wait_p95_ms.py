"""Scheduler: 95th percentile of due time to the step that admitted the
request (requests still waiting count with the wait they have had)."""


def read(ctx):
    return ctx.summary["queue_wait_p95_ms"]
