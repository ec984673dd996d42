"""Per-request kernel calls of the latent diffusion family: text encoder,
``denoise_steps`` UNet calls, VAE decode."""

from __future__ import annotations

from counts.text import text_calls
from counts.unet import unet_calls
from counts.vae import vae_calls


def stage_calls(cfg: dict, B: int, prompt_len: int) -> list:
    """[(stage, repeats, calls of one dispatch at batch B)]."""
    hw = cfg["image_size"] // cfg["latent_down"]
    stages = [("text_encoder", 1, text_calls(cfg["text"], B, prompt_len)),
              ("denoise", cfg["denoise_steps"],
               unet_calls(cfg["unet"], B, hw, prompt_len))]
    if cfg.get("vae"):
        stages.append(("vae", 1, vae_calls(cfg["vae"], B, hw)))
    return stages
