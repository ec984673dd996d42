"""Reference text encoder: token and learned position embeddings, pre-LN
transformer layers (bidirectional attention, GELU MLP), final LayerNorm."""

from __future__ import annotations

from reference import nn


def encode(p, tokens, t: dict, dt):
    x = p["embed"]["table"][tokens].astype(nn.dtype(dt))
    x = x + p["pos"][: tokens.shape[1]].astype(nn.dtype(dt))[None]
    for i in range(t["n_layers"]):
        lp = p[f"layer{i}"]
        h = nn.layer_norm(lp["ln1"], x, dt)
        x = x + nn.mha(lp["attn"], h, h, t["n_heads"], dt)
        h = nn.layer_norm(lp["ln2"], x, dt)
        x = x + nn.dense(lp["mlp"]["wo"], nn.gelu(nn.dense(lp["mlp"]["wi"],
                                                          h, dt)), dt)
    return nn.layer_norm(p["final_ln"], x, dt)
