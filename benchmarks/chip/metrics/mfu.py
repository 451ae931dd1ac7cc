"""mfu (%): the FLOPs the clients' local SGD needs -- C*H*b samples per
round, 3 x the model's forward FLOPs per sample (forward + backward) --
times the window's rounds, over window seconds x chips x the bf16 peak.
Host clock; the shape function is the model file's ``forward_flops``."""


def read(ctx):
    if ctx.peaks is None:
        return None
    shape = ctx.traffic["cohort"]
    samples = (shape["clients_per_round"] * shape["local_steps"]
               * shape["local_batch"])
    flops = 3.0 * ctx.model.forward_flops(ctx.config["model_config"])
    return (100.0 * samples * flops * ctx.window_rounds
            / (ctx.window_s * ctx.chips * ctx.peaks.flops_bf16))
