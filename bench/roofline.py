"""A kernel family's share of its roofline over the traced window.

The counted calls are what the served pods must have launched, from the
configuration: for each pod, each stage's calls at the pod's batch, times
the stage's repeats.  The traced calls are the Pallas kernel ops whose
operand ranks mark them as the family's (``FAMILIES``).  The two
must agree in number, or the reading is refused.  The share is the least
time the chip could take for the counted calls (each call the larger of
operations over peak FLOP/s and bytes over peak bandwidth) over the
kernels' device time.
"""

from __future__ import annotations

from devtrace import matching

# Operand ranks of each kernel family's calls: the 2-D conv takes its input
# as (B, rows, row phases, columns, channels); the temporal conv (B, F, N,
# C), (K, C, C_out), (1, C_out); attention (flash and temporal) three
# rank-4 tensors; GroupNorm (B, N, C) and two (1, C).
FAMILIES = {
    "conv": lambda r: (len(r) >= 2 and r[0] == 5) or r == (4, 3, 2),
    "attention": lambda r: r == (4, 4, 4),
    "groupnorm": lambda r: r == (3, 2, 2),
}


def counted(run: dict, kind: str) -> tuple:
    """(number of calls, least seconds) of ``kind`` served in the window."""
    pk = run["peaks"]
    n, least = 0, 0.0
    for pod in run["pods"]:
        for _, rep, calls in run["stage_calls"](run["config"], pod["requests"],
                                                run["prompt_len"]):
            for c in calls:
                if c.kind == kind:
                    n += rep
                    least += rep * max(c.flops / pk["flops_per_s"],
                                       c.bytes / pk["hbm_bytes_per_s"])
    return n, least


def share(run: dict, kind: str) -> float | None:
    if run["trace"] is None:
        return None
    n, least = counted(run, kind)
    if n == 0:
        return None
    ops = next(iter(run["trace"]["devices"].values()))["ops"]
    found = matching(ops, FAMILIES[kind])
    if len(found) != n:
        raise RuntimeError(
            f"{kind}: counted {n} kernel calls in the served pods but the "
            f"trace holds {len(found)} {kind} kernel ops")
    return 100.0 * least / sum(e - s for _, _, s, e in found)
