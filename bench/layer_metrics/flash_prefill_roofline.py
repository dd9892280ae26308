"""Flash-prefill kernel's share of its roofline: the least time of every
kernel call (one per layer per prefill; the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, at the unpadded causal length) over
the summed device time of the kernel's events, both over the bursts the
trace covers. The kernel's events are matched by the stable prefix of
their name."""
from bench import trace
from bench.spec import load_counts

KERNEL = "flash_attention"


def read(ctx):
    dev = trace.device_time(ctx.trace, "ops", KERNEL, ctx.region)
    if not ctx.prefills or dev <= 0:
        return None
    counts = load_counts("flash_prefill")
    pk = ctx.peaks
    least = compute = 0.0
    for S in ctx.covered_prefills:
        c = counts.flops(ctx.cfg, S) / pk["bf16_flops"]
        m = counts.bytes(ctx.cfg, S) / pk["hbm_bytes_per_s"]
        least += ctx.cfg["num_hidden_layers"] * max(c, m)
        compute += ctx.cfg["num_hidden_layers"] * c
    ctx.notes.append("flash_prefill_roofline bound by "
                     + ("compute" if compute >= least else "memory"))
    return 100.0 * least / dev
