"""Host: the scheduler's own host time per ``step()`` in the traced span,
the ``serving.step`` annotations less the ``serving.wait`` ones in them
(where the host is blocked on the device), over the number of steps
(profiler's host plane)."""
from bench import spans


def read(ctx):
    return spans.host_ms_per_step(spans.serving_events(ctx))
