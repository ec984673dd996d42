"""Kernel calls of one UNet forward, walked from the configuration.

The walk follows the UNet's published layout: per level ``num_res_blocks``
ResNet blocks, each followed by a spatial transformer where the level has
attention, then a stride-2 conv; a middle ResNet-attention-ResNet; and the
mirror image going up, with one more ResNet block per level that takes the
skip connection, and a nearest-neighbour upsample followed by a conv.
ResNet blocks run as two fused conv kernels (GroupNorm applied in the
kernel, time embedding and residual in its epilogue) plus a 1x1 conv when
the channel count changes.
"""

from __future__ import annotations

from counts import common


def plan(u: dict) -> dict:
    """{"down": [[(kind, c_in, c_out), ...] per level], "mid": [...],
    "up": [...]}: the blocks in execution order."""
    ch, mults, nrb = u["model_channels"], u["channel_mult"], u["num_res_blocks"]
    attn = set(u["attn_levels"])
    c_cur, skips, down, up = ch, [ch], [], []
    for level, mult in enumerate(mults):
        blocks = []
        for _ in range(nrb):
            blocks.append(("res", c_cur, ch * mult))
            c_cur = ch * mult
            if level in attn:
                blocks.append(("attn", c_cur, c_cur))
            skips.append(c_cur)
        if level != len(mults) - 1:
            blocks.append(("down", c_cur, c_cur))
            skips.append(c_cur)
        down.append(blocks)
    mid = [("res", c_cur, c_cur), ("attn", c_cur, c_cur), ("res", c_cur, c_cur)]
    for level in reversed(range(len(mults))):
        blocks = []
        for _ in range(nrb + 1):
            blocks.append(("res", c_cur + skips.pop(), ch * mults[level]))
            c_cur = ch * mults[level]
            if level in attn:
                blocks.append(("attn", c_cur, c_cur))
        if level != 0:
            blocks.append(("up", c_cur, c_cur))
        up.append(blocks)
    return {"down": down, "mid": mid, "up": up}


def blocks(u: dict, hw: int):
    """Yield (name, kind, c_in, c_out, side) in execution order, ``side``
    being the block's spatial size at its input."""
    p = plan(u)
    for si, level in enumerate(p["down"]):
        for bi, (kind, ci, co) in enumerate(level):
            yield f"down_{si}_{bi}_{kind}", kind, ci, co, hw
            if kind == "down":
                hw //= 2
    for bi, (kind, ci, co) in enumerate(p["mid"]):
        yield f"mid_{bi}_{kind}", kind, ci, co, hw
    for si, level in enumerate(p["up"]):
        for bi, (kind, ci, co) in enumerate(level):
            yield f"up_{si}_{bi}_{kind}", kind, ci, co, hw
            if kind == "up":
                hw *= 2


def heads(u: dict, c: int) -> int:
    return u["n_heads"] or max(1, c // u["head_channels"])


def res_calls(name, B, hw, ci, co, temb_dim) -> list:
    calls = [common.linear(f"{name}/temb", B, temb_dim, co)]
    if ci != co:
        calls.append(common.conv2d(f"{name}/skip", B, hw, hw, ci, co, 1))
    calls.append(common.conv2d(f"{name}/conv1", B, hw, hw, ci, co, 3,
                               temb=True, gn=True, stats=True))
    calls.append(common.conv2d(f"{name}/conv2", B, hw, hw, co, co, 3,
                               gn=True, residual=True))
    return calls


def transformer_calls(u: dict, name, B, hw, c, ctx_len) -> list:
    n, h = B * hw * hw, heads(u, c)
    calls = [common.linear(f"{name}/proj_in", n, c, c)]
    cross = u["cross_attn"] and ctx_len
    if cross:
        calls.append(common.linear(f"{name}/ctx_proj", B * ctx_len,
                                   u["context_dim"], c, bias=False))
    for i in range(u["tf_depth"]):
        p = f"{name}/layer{i}"
        calls += [common.linear(f"{p}/self_{w}", n, c, c, bias=False)
                  for w in "qkvo"]
        calls.append(common.attention(f"{p}/self_attn", B, h, hw * hw,
                                      hw * hw, c // h))
        if cross:
            calls += [common.linear(f"{p}/cross_{w}", n, c, c, bias=False)
                      for w in "qo"]
            calls += [common.linear(f"{p}/cross_{w}", B * ctx_len, c, c,
                                    bias=False) for w in "kv"]
            calls.append(common.attention(f"{p}/cross_attn", B, h, hw * hw,
                                          ctx_len, c // h))
        calls += [common.linear(f"{p}/ff_{w}", n, c, 4 * c)
                  for w in ("gate", "in")]
        calls.append(common.linear(f"{p}/ff_out", n, 4 * c, c))
    calls.append(common.linear(f"{name}/proj_out", n, c, c))
    return calls


def unet_calls(u: dict, B: int, hw: int, ctx_len: int, hook=None) -> list:
    """Calls of one UNet forward at batch ``B`` and latent side ``hw``.
    ``hook(name, c, side)`` adds the calls a video UNet puts after each
    spatial attention block."""
    mc, temb = u["model_channels"], 4 * u["model_channels"]
    calls = [common.linear("temb1", B, mc, temb),
             common.linear("temb2", B, temb, temb),
             common.conv2d("conv_in", B, hw, hw, u["in_channels"], mc, 3)]
    for name, kind, ci, co, side in blocks(u, hw):
        if kind == "res":
            calls += res_calls(name, B, side, ci, co, temb)
        elif kind == "attn":
            calls += transformer_calls(u, name, B, side, co, ctx_len)
            if hook is not None:
                calls += hook(name, co, side)
        elif kind == "down":
            calls.append(common.conv2d(name, B, side, side, ci, co, 3,
                                       stride=2))
        else:
            calls.append(common.conv2d(name, B, 2 * side, 2 * side, ci, co, 3))
    calls.append(common.conv2d("conv_out", B, hw, hw, mc, u["out_channels"],
                               3, gn=True))
    return calls
