"""Host time per decode step inside the executor: the wall time of the
``decode_batch`` calls, minus the device time of the decode-step program
inside them, over the number of steps; all three over the bursts the
trace covers."""
from bench import trace

PROGRAM = "jit_decode_step"


def read(ctx):
    if not ctx.decode_steps:
        return None
    region, steps = ctx.region, ctx.covered_decode_steps
    wall = trace.span_time(ctx.trace, "bench.decode_batch", region)
    dev = trace.device_time(ctx.trace, "modules", PROGRAM, region)
    return 1e3 * (wall - dev) / len(steps)
