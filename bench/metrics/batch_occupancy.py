"""Scheduler: decode rows filled with a live sequence, as a share of the
slots every decode tick offers (counters)."""


def read(ctx):
    c = ctx.counters
    if not c["decode_steps"]:
        return None
    return 100.0 * (c["tokens_out"] - c["prefills"]) / (
        c["decode_steps"] * ctx.max_slots)
