"""The starting noise of a request's denoise stage.

The serving contract draws it from the serve seed folded with the request
id and then with the stage's index, one key per request, as standard
normal samples.  The reference draws it again from the seed itself."""

from __future__ import annotations

import jax


def stage_noise(serve_seed: int, rid: int, stage_index: int, shape, dtype):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(serve_seed), rid), stage_index)
    return jax.random.normal(key, shape, dtype)
