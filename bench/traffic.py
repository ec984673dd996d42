"""The one traffic generator: prompts and arrivals from a traffic file.

A traffic file (``bench/traffic/<name>.json``) gives:

* ``loop``: ``closed``, the only loop generated: ``clients`` callers, each
  sending its next request when its last returns;
* ``pod_size``: how many requests the server batches into one pod;
* ``prompt``: ``padded_len`` tokens per prompt, of which the first ``k``
  are drawn and the rest are the pad id (the last id of the vocabulary),
  never more than the model's context;
  ``k`` follows a log-normal law (``median``, ``sigma``) clipped to
  [``min_tokens``, ``max_tokens``].

Every seed gets the same multiset of prompt lengths (the law's quantiles),
in another order, and its own token ids: the amount of work does not
depend on the seed.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

POOL = 512


class Traffic:
    def __init__(self, spec: dict, seed: int, vocab: int, max_len: int):
        if spec["loop"] != "closed":
            raise ValueError(f"only closed-loop traffic is generated, not "
                             f"{spec['loop']!r}")
        self.spec = spec
        p = spec["prompt"]
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7a11c]))
        law = statistics.NormalDist(math.log(p["median"]), p["sigma"])
        lens = [round(math.exp(law.inv_cdf((i + 0.5) / POOL)))
                for i in range(POOL)]
        self.padded_len = min(p["padded_len"], max_len)
        lens = np.clip(lens, p["min_tokens"], min(p["max_tokens"],
                                                 self.padded_len))
        self.lengths = rng.permutation(lens)
        self.pad_id = vocab - 1
        self.tokens = rng.integers(0, vocab - 1, size=(POOL, self.padded_len))

    @property
    def pod_size(self) -> int:
        return self.spec["pod_size"]

    @property
    def clients(self) -> int:
        return self.spec["clients"]

    def prompt(self, i: int) -> np.ndarray:
        """The ``i``-th prompt of the run: ``padded_len`` int32 ids."""
        row = self.tokens[i % POOL].copy()
        row[int(self.lengths[i % POOL]):] = self.pad_id
        return row.astype(np.int32)
