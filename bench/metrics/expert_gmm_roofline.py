"""Share of its roofline that the grouped expert matmuls (the Pallas
kernels ``expert_gmm`` and ``expert_tgmm``) reached in the traced window,
in percent: the least time their work could take on this chip
(``roofline/expert_gmm.py``, from the held routes the program counted,
``/cdfl/moe/routes_held``, per round of the window, times the traced
rounds) over the device time of the kernels' events."""
from benchlib import counters, metrics, spec

SCALARS = counters.listen()
KERNELS = ("expert_gmm", "expert_tgmm")


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.window()
    held = SCALARS.total("/cdfl/moe/routes_held", lo, hi)
    spent = sum(ctx.kernel_ns(k) for k in KERNELS)
    if held <= 0 or spent <= 0:
        return None
    cfg, fleet = ctx.config, ctx.config["fleet"]
    rounds = ctx.traced_rounds * ctx.variants
    flops, nbytes = spec.module("roofline", "expert_gmm").cost({
        "routes": held / ctx.rounds * ctx.traced_rounds,
        "invocations": (rounds * fleet["num_nodes"] * fleet["local_steps"]
                        * cfg["num_hidden_layers"]),
        "hidden": cfg["hidden_size"], "width": cfg["moe_intermediate_size"],
        "experts": cfg["num_experts"]})
    pk = metrics.peaks(ctx.device["kind"])
    least = max(flops / pk["bf16_flops_per_s"],
                nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (spent / 1e9)
