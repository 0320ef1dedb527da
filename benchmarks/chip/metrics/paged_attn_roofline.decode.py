"""The paged attention kernel's share (%) of its roofline in the chat
cell; moves ``itl_p95_ms``.

Work: QK^T and PV over each request's live causal context, K/V read once
per request and call, from the lengths of the requests sent
(``chipbench.counts.attn_call``), per call the larger of the compute and
byte bounds. Time: the device durations of the kernel's events, matched by
``NAMES``."""

from chipbench import trace

NAMES = ("paged_prefill_attention_kernel",)


def read(ctx):
    bound = ctx.inputs.get("work", {}).get("attn_bound_s", 0.0)
    events = [e for v in ctx.device.values() for e in trace.matching(v, NAMES)]
    t = sum(d for _, _, d in events) / 1e9
    if bound <= 0 or t <= 0:
        return None
    return 100.0 * bound / t
