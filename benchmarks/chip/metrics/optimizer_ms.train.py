"""Device ms per step of the train step's ``optimizer`` region (unscale and
finite check, gradient sync, clipping, the AdamW update and the non-finite
guard), averaged over the cell's devices; moves ``train_tokens_per_s``
(``chipbench.scopes``)."""

from chipbench import scopes


def read(ctx):
    steps = ctx.inputs.get("steps", 0)
    sec = scopes.seconds(ctx)
    if not steps or not sec or not sec.get("optimizer"):
        return None
    return 1e3 * sec["optimizer"] / ctx.n_devices / steps
