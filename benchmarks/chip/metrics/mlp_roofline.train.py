"""The MLP's share (%) of its roofline in training; moves
``train_tokens_per_s``.

Work: the MLP matmuls forward and backward without recomputation, 3 x 2 x
d x d_ff operations per token per layer for each matmul (3 matmuls when
gated: gate, up, down), compute-bound, times the tokens of the window's
steps. Time: the device durations of the ops in the ``mlp`` region
(``chipbench.scopes``), recomputed forwards included."""

from chipbench import scopes


def read(ctx):
    steps = ctx.inputs.get("steps", 0)
    sec = scopes.seconds(ctx)
    if not steps or not sec or not sec.get("mlp"):
        return None
    c, mix = ctx.config, ctx.mix
    matmuls = 3 if c["mlp_gated"] else 2
    tokens = mix["global_batch"] * mix["seq_len"] * steps
    flops = (3 * matmuls * 2 * c["hidden_size"] * c["intermediate_size"]
             * c["num_hidden_layers"] * tokens)
    return 100.0 * flops / ctx.peaks["bf16_flops"] / sec["mlp"]
