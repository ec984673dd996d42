"""Per-request kernel calls of the Make-A-Video family: text encoder, the
first half of the DDIM schedule with frames folded into the batch
(spatial only), the second half through the video UNet."""

from __future__ import annotations

from counts.text import text_calls
from counts.ttv import video_unet_calls
from counts.unet import unet_calls


def split(steps: int) -> tuple:
    """(keyframe, temporal) steps: a schedule of one step cannot be split."""
    return (0, steps) if steps < 2 else (steps // 2, steps - steps // 2)


def stage_calls(cfg: dict, B: int, prompt_len: int) -> list:
    hw, F = cfg["image_size"] // cfg["latent_down"], cfg["frames"]
    kf, tp = split(cfg["denoise_steps"])
    stages = [("text_encoder", 1, text_calls(cfg["text"], B, prompt_len))]
    if kf:
        stages.append(("keyframe_denoise", kf,
                       unet_calls(cfg["unet"], B * F, hw, prompt_len)))
    stages.append(("temporal_denoise", tp, video_unet_calls(
        cfg["unet"], B, F, hw, prompt_len, cfg["temporal_head_channels"])))
    return stages
