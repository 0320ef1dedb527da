"""Model FLOP/s utilization (%) of the training step: forward and backward
operations per step without recomputation (``chipbench.counts.
train_step_flops``) times the steps of the traced window, over the window
times the chips times the bf16 peak; moves ``train_tokens_per_s``."""

from chipbench import counts


def read(ctx):
    steps = ctx.inputs.get("steps", 0)
    if not steps:
        return None
    mix = ctx.mix
    flops = counts.train_step_flops(ctx.config, mix["global_batch"],
                                    mix["seq_len"]) * steps
    return 100.0 * flops / (ctx.window_s * ctx.n_devices
                            * ctx.peaks["bf16_flops"])
