"""Host time per decode step inside the executor: the wall time of the
``decode_batch`` calls, minus the device time of the decode-step program
inside them, over the number of steps."""
from bench import trace

PROGRAM = "jit_decode_step"


def read(ctx):
    if not ctx.decode_steps:
        return None
    lo, hi = trace.window(ctx.trace)
    wall = sum(min(e, hi) - max(s, lo)
               for s, e in trace.spans(ctx.trace, "bench.decode_batch")
               if e > lo and s < hi)
    dev = trace.device_time(ctx.trace, "modules", PROGRAM)
    return 1e3 * (wall - dev) / len(ctx.decode_steps)
