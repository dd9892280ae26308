"""Decode step's share of the peak: the model FLOPs of the decode steps
over the device time of the decode program x peak FLOP/s, both over the
bursts the trace covers."""
from bench import trace
from bench.spec import load_counts

PROGRAM = "jit_decode_step"


def read(ctx):
    dev = trace.device_time(ctx.trace, "modules", PROGRAM, ctx.region)
    if not ctx.decode_steps or dev <= 0:
        return None
    counts = load_counts("decode_step", ctx.cfg, ctx.root)
    flops = sum(counts.flops(ctx.cfg, c) for c in ctx.covered_decode_steps)
    return 100.0 * flops / (dev * ctx.peaks["bf16_flops"])
