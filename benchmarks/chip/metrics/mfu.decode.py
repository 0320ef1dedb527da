"""The whole engine step's share (%) of the chip's roofline in the chat
cell; moves ``itl_p95_ms``.

For each device call in the window, the least time the chip needs for it
(``chipbench.counts.serve_call``: the operations of every row processed,
weights once per decode step, live KV read and new KV written, the larger
of the compute and the byte bound), summed, over the device's busy time.
For memory-bound decode only this share bounds a gain."""


def read(ctx):
    bound = ctx.inputs.get("work", {}).get("step_bound_s", 0.0)
    busy = sum(ctx.busy_s.values()) / len(ctx.busy_s)
    if bound <= 0 or busy <= 0:
        return None
    return 100.0 * bound / busy
