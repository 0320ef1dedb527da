"""The LM head's share (%) of its roofline in training; moves
``train_tokens_per_s``.

Work: the head matmul forward, its weight gradient and its input gradient,
6 x d x V operations per token, compute-bound, times the tokens of the
window's steps (a chunked head's logit recompute counts as time, not work).
Time: the device durations of the ops in the ``lm_head`` region (the head
matmul and the cross entropy with its z-loss; ``chipbench.scopes``)."""

from chipbench import scopes


def read(ctx):
    steps = ctx.inputs.get("steps", 0)
    sec = scopes.seconds(ctx)
    if not steps or not sec or not sec.get("lm_head"):
        return None
    c, mix = ctx.config, ctx.mix
    tokens = mix["global_batch"] * mix["seq_len"] * steps
    flops = 6 * c["hidden_size"] * c["vocab_size"] * tokens
    return 100.0 * flops / ctx.peaks["bf16_flops"] / sec["lm_head"]
