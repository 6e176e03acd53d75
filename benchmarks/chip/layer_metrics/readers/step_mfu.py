"""The whole step's share of the chip's peak: the model's operations
over the scope the driver read them in (the traced slice of a traced
run) / its seconds / peak, per chip."""


def read(trace, run, args, ctx):
    if ctx.peaks is None:
        return None
    t_open, t_close = run["layer_scope"]
    if not run.get("model_flops"):
        return None
    return (100.0 * run["model_flops"] / (t_close - t_open)
            / (ctx.peaks["bf16_flops_per_s"] * ctx.device["count"]))
