"""Model FLOPs of served tokens, from shapes alone.

A request with prompt length P that was served N tokens ran P prompt
positions and N - 1 decode positions (the last served token is never fed
back), and the LM head once per served token.  A position t (0-based)
costs 2 x (the matmul parameters of every block), plus attention over
the t + 1 keys it sees: 2 x 2 x heads x head_dim x (t + 1) per block
(scores and the weighted sum).  Not counted: rejected drafts, the edge's
draft passes, bucket padding, and the prefill's logits at prompt
positions other than the last.
"""
from __future__ import annotations

from bench.weights import Model


def request_flops(m: Model, prompt_len: int, served: int) -> float:
    positions = prompt_len + max(served - 1, 0)
    linear = 2.0 * m.block_matmul_params() * m.num_hidden_layers * positions
    # sum over t of (t + 1) for t < positions
    keys = positions * (positions + 1) / 2.0
    attn = 4.0 * m.num_attention_heads * m.head_dim * m.num_hidden_layers \
        * keys
    head = 2.0 * m.hidden_size * m.vocab_size * served
    return linear + attn + head
