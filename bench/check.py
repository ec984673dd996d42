"""Whether what the timed path served is correct.

Every request served in the window is checked, stage by stage, against
the plain reference: the reference stage is given the state the served
stage was given and should return what the served stage returned.  The
last stage is compared with what the engine handed back to the client.
A stage's number is the largest, over the requests, of
max |served - reference| / max |reference|.

The handoffs are checked exactly: each stage must have been given the
request's own prompt, or the very row that the stage before it returned
for the same request, so that a stage fed another request's state, or a
stale one, fails even where each stage alone is right.
"""

from __future__ import annotations

import math

import numpy as np


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    scale = float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want)) / max(scale, 1e-30))


def handoff_err(ref, records: dict, prompts: dict) -> float:
    """The largest |difference| between what a stage was given and what
    it should have been given: the request's prompt for the first stage,
    the previous stage's output row for the others."""
    worst = 0.0
    for r, rec in records.items():
        prev = None
        for stage, key in ref.STAGES:
            if stage not in rec:
                return math.inf
            if prev is None:
                want, got = prompts[r], row(rec[stage], 0, "tokens")
            else:
                want = row(rec[prev[0]], 1, prev[1])
                got = row(rec[stage], 0, prev[1])
            if np.shape(got) != np.shape(want):
                return math.inf
            diff = np.abs(np.asarray(got, np.float64)
                          - np.asarray(want, np.float64))
            worst = max(worst, float(np.max(diff, initial=0.0)))
            prev = (stage, key)
    return worst


def numbers(ref, cfg: dict, params, records: dict, served: dict,
            prompts: dict, serve_seed: int, block: int, mode) -> dict:
    """{"handoff_err": value, "<stage>_err": value} over every request.

    ``records[rid][stage] = (state_in, state_out, row)``: the batched
    state each served stage was given and returned, and the request's row
    in it; ``served[rid]`` is what the engine returned for it and
    ``prompts[rid]`` what was submitted.  The reference runs on ``block``
    requests at a time."""
    import jax

    rids = sorted(records)
    out = {"handoff_err": handoff_err(ref, records, prompts) if rids
           else math.inf}
    last = ref.STAGES[-1][0]
    for stage, key in ref.STAGES:
        errs = [math.inf] if not rids else []
        for i in range(0, len(rids), block):
            part = rids[i:i + block]
            state = {k: np.stack([row(records[r][stage], 0, k) for r in part])
                     for k in records[part[0]][stage][0]}
            want = np.asarray(jax.device_get(ref.stage(
                cfg, stage, params, state, part, serve_seed, mode)),
                np.float64)
            got = [served[r] if stage == last
                   else row(records[r][stage], 1, key) for r in part]
            errs += [rel_err(g, w) for g, w in zip(got, want)]
        out[f"{stage}_err"] = max(errs)
    return out


def row(record, side: int, key: str):
    """One request's row of a recorded batched state, on the host."""
    return np.asarray(record[side][key])[record[2]]


def verdict(nums: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}})."""
    lim = limits["limits"]
    table = {k: {"value": v, "limit": lim[k]} for k, v in nums.items()}
    ok = all(math.isfinite(v) and v <= lim[k] for k, v in nums.items())
    return ok and set(nums) == set(lim), table
