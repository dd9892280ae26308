"""Share of the traced window in which the host is inside no executor
call (prefill, decode_batch): the fleet loop, the scheduler, cluster
set-up and the collector."""
from bench import trace


def read(ctx):
    return 100.0 * (1.0 - trace.host_share(ctx.trace, trace.EXECUTOR_SPANS))
