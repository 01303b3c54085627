"""Model FLOP/s utilization of the whole step, in percent: samples in the
traced window x the FLOPs of one sample as the configuration's model
family counts them (forward and backward of the local phase; evaluation
and mixing not counted), over the window's length x the chip's bf16
peak."""
from benchlib import metrics


def read(ctx):
    if ctx.trace is None:
        return None
    pk = metrics.peaks(ctx.device["kind"])
    busy, span = ctx.busy_window()
    flops = (ctx.samples_per_round * ctx.traced_rounds
             * ctx.family.flops_per_sample(ctx.config))
    return 100.0 * flops / (span * pk["bf16_flops_per_s"])
