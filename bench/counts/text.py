"""Kernel calls of the text encoder: a pre-LN bidirectional transformer
(attention with biased projections, a GELU MLP)."""

from __future__ import annotations

from counts import common


def text_calls(t: dict, B: int, S: int) -> list:
    d, h, ff = t["d_model"], t["n_heads"], t["d_ff"]
    calls = []
    for i in range(t["n_layers"]):
        p = f"text/layer{i}"
        calls += [common.linear(f"{p}/{w}", B * S, d, d) for w in "qkvo"]
        calls.append(common.attention(f"{p}/attn", B, h, S, S, d // h))
        calls.append(common.linear(f"{p}/mlp_in", B * S, d, ff))
        calls.append(common.linear(f"{p}/mlp_out", B * S, ff, d))
    return calls
