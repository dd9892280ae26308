"""Device idle share: 1 minus the union of device-op intervals over the
traced window; over several chips, busy time is averaged over them."""
from bench import trace


def read(ctx):
    if not ctx.trace.devices:
        return None
    lo, hi = trace.window(ctx.trace)
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / (hi - lo))
