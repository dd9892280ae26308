"""Device idle share: 1 minus the union of device-op intervals over the
stretches of the window that the trace covers; over several chips, busy
time is averaged over them."""
from bench import trace


def read(ctx):
    if not ctx.trace.devices:
        return None
    region = ctx.region
    return 100.0 * (1.0 - trace.busy_s(ctx.trace, region)
                    / trace.length(region))
