"""The flash attention kernels' share (%) of their roofline in one-chip
training; moves ``train_tokens_per_s``.

Work: causal attention forward and backward without recomputation
(``chipbench.counts.flash_attn_flops``), compute-bound, per step times the
steps of the window. Time: the device durations of the forward and
backward kernels' events (``NAMES``), recomputed forwards included."""

from chipbench import counts, trace

NAMES = ("flash_attention_fwd_flat", "flash_attention_bwd_flat")


def read(ctx):
    steps = ctx.inputs.get("steps", 0)
    events = [e for v in ctx.device.values() for e in trace.matching(v, NAMES)]
    t = sum(d for _, _, d in events) / 1e9
    if not steps or t <= 0:
        return None
    mix = ctx.mix
    flops = counts.flash_attn_flops(ctx.config, mix["global_batch"],
                                    mix["seq_len"]) * steps
    return 100.0 * flops / ctx.peaks["bf16_flops"] / t
