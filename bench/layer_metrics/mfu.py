"""Whole-run model FLOPs utilization: the model FLOPs of every prefill
and decode step in the traced window, over window x chips x peak."""
from bench import trace
from bench.spec import load_counts


def read(ctx):
    lo, hi = trace.window(ctx.trace)
    prefill, decode = (load_counts(name, ctx.cfg, ctx.root)
                       for name in ("prefill_step", "decode_step"))
    flops = (sum(prefill.flops(ctx.cfg, S) for S in ctx.prefills)
             + sum(decode.flops(ctx.cfg, c) for c in ctx.decode_steps))
    if not flops:
        return None
    return 100.0 * flops / ((hi - lo) * ctx.chips * ctx.peaks["bf16_flops"])
