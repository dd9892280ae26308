"""Prefill step's share of the peak: the model FLOPs of the prefills
over the device time of the prefill program x peak FLOP/s, both over the
bursts the trace covers."""
from bench import trace
from bench.spec import load_counts

PROGRAM = "jit_prefill"


def read(ctx):
    dev = trace.device_time(ctx.trace, "modules", PROGRAM, ctx.region)
    if not ctx.prefills or dev <= 0:
        return None
    flops = sum(load_counts("prefill_step").flops(ctx.cfg, S)
                for S in ctx.covered_prefills)
    return 100.0 * flops / (dev * ctx.peaks["bf16_flops"])
