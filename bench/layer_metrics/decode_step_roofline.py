"""Decode step's share of its roofline: the least time of every decode
step (the larger of FLOPs over peak FLOP/s and the bytes the algorithm
needs over peak bytes/s) over the summed device time of the decode-step
program, both over the bursts the trace covers."""
from bench import trace
from bench.spec import load_counts

PROGRAM = "jit_decode_step"


def read(ctx):
    dev = trace.device_time(ctx.trace, "modules", PROGRAM, ctx.region)
    if not ctx.decode_steps or dev <= 0:
        return None
    counts = load_counts("decode_step", ctx.cfg, ctx.root)
    pk = ctx.peaks
    least = memory = 0.0
    for c in ctx.covered_decode_steps:
        f = counts.flops(ctx.cfg, c) / pk["bf16_flops"]
        m = counts.bytes(ctx.cfg, c) / pk["hbm_bytes_per_s"]
        least += max(f, m)
        memory += m
    ctx.notes.append("decode_step_roofline bound by "
                     + ("memory" if memory >= least else "compute"))
    return 100.0 * least / dev
