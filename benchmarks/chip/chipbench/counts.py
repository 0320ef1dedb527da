"""Operations and bytes the algorithm needs, from the model's shapes and the
lengths of the requests actually sent — never from a kernel's grid, block
tables or pages walked. Configurations are the dicts of ``configs/*.json``.

Conventions: a multiply-add is 2 operations; causal attention over a row at
absolute position ``p`` reads ``p + 1`` keys; bytes are HBM bytes at the
itemsize the configuration serves or trains in.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple


def dims(c: Dict) -> Tuple[int, int, int, int, int, int, int]:
    return (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"], c["num_hidden_layers"])


def layer_matmul_params(c: Dict) -> int:
    d, H, Kv, hd, F, _, _ = dims(c)
    mlp = (3 if c["mlp_gated"] else 2) * d * F
    return d * H * hd + 2 * d * Kv * hd + H * hd * d + mlp


def head_params(c: Dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def causal_keys(start: int, rows: int) -> int:
    """Keys read by ``rows`` query rows at positions start .. start+rows-1."""
    return rows * start + rows * (rows + 1) // 2


# ------------------------------------------------------------ attention
def attn_call(c: Dict, rows: Sequence[Tuple[int, int]], itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one attention call over ONE layer, for requests
    given as (start, n_rows): QK^T and PV over the live causal context,
    K/V read once per request up to its last row, q read and o written."""
    _, H, Kv, hd, _, _, _ = dims(c)
    keys = sum(causal_keys(s, t) for s, t in rows)
    flops = 4.0 * H * hd * keys
    ctx = sum(s + t for s, t in rows)
    n_rows = sum(t for _, t in rows)
    byts = itemsize * (2.0 * Kv * hd * ctx + 2.0 * H * hd * n_rows)
    return flops, byts


def bound_s(flops: float, byts: float, peaks: Dict[str, float]) -> float:
    """Least time for (flops, bytes) on the chip: the larger of the two."""
    return max(flops / peaks["bf16_flops"], byts / peaks["hbm_bw"])


# ------------------------------------------------------------- serving
def serve_call(c: Dict, prefill: Sequence[Tuple[int, int]],
               decode_steps: Sequence[Sequence[int]], prefill_sampled: int = 0,
               itemsize: int = 2):
    """Work of one engine device call.

    ``prefill``: (start, rows) of the prompt chunk(s) in the call, of
    which ``prefill_sampled`` end a prompt (their last row is sampled).
    ``decode_steps``: per decode step, the positions of the tokens fed
    (each attends to position + 1 keys).

    Returns {"attn": (flops, bytes) summed over layers, "step": (flops,
    bytes) of the whole call}: weights read once per decode step (and once
    for a call that only prefills), KV of the live context read, new KV
    written, and the matmul and attention operations of every row
    processed; the head only for rows whose logits are sampled."""
    d, H, Kv, hd, F, V, L = dims(c)
    rows = [(s, t) for s, t in prefill]
    for step in decode_steps:
        rows += [(p, 1) for p in step]
    af, ab = attn_call(c, rows, itemsize)
    af, ab = af * L, ab * L
    n_tok = sum(t for _, t in rows)
    n_head = prefill_sampled + sum(len(s) for s in decode_steps)
    w_reads = max(len(decode_steps), 1)
    flops = 2.0 * n_tok * L * layer_matmul_params(c) + af \
        + 2.0 * n_head * head_params(c)
    kv_write = itemsize * 2.0 * Kv * hd * L * n_tok
    weights = itemsize * (L * layer_matmul_params(c) + head_params(c))
    byts = w_reads * weights + ab + kv_write
    return {"attn": (af, ab), "step": (flops, byts)}


# ------------------------------------------------------------ training
def train_step_flops(c: Dict, batch: int, seq: int) -> float:
    """Forward + backward operations of one step, without recomputation:
    6 per parameter per token for the matmuls and the head, and 12 per
    (query, key, head dim) pair for causal attention (4 forward, 8
    backward)."""
    d, H, Kv, hd, F, V, L = dims(c)
    tokens = batch * seq
    mm = 6.0 * tokens * (L * layer_matmul_params(c) + head_params(c))
    return mm + flash_attn_flops(c, batch, seq)


def flash_attn_flops(c: Dict, batch: int, seq: int) -> float:
    """Causal attention forward + backward of one step, no recomputation."""
    _, H, _, hd, _, _, L = dims(c)
    return 12.0 * H * hd * L * batch * causal_keys(0, seq)
