#!/usr/bin/env python3
"""Readings that the correctness limits are set from.

    python3 bench/control.py --workload sd512-c4 --seeds 1,2,3 --control 3

In one process (set-up is long, so it is paid once), for each seed: make
the weights and traffic, serve one pod of the cell's shape through the
program's engine, and read the numbers the benchmark compares.  For the
first ``--control`` seeds, also read the control: the plain reference in
the next precision below the configuration's (float32 matmuls at three
bfloat16 passes where the configuration states full float32), put in the
program's place on the same prompts and noise.  One JSON line
per seed goes to stdout.  A limit lies above every program reading and
below every control reading that is at least three times the largest
program reading.

Needs a TPU, like the benchmark itself; the CPU test runs it at a reduced
size through :func:`control_numbers`.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))


def control_numbers(cell: dict, params, win: dict, serve_seed: int) -> dict:
    """The compared numbers with the control in the program's place: the
    reference in the limits file's ``control`` mode, the next precision
    below the configuration's.  Each control stage runs on what the
    previous control stage handed on, and is compared with the reference
    given the same input."""
    import numpy as np

    import harness
    import spec
    from check import row

    conf = cell["config"]["config"]
    mode = cell["limits"]["control"]
    ref = spec.load_module("reference", conf["family"])
    rids = sorted(win["records"])
    first = ref.STAGES[0][0]
    state = {"tokens": np.stack([row(win["records"][r][first], 0, "tokens")
                                 for r in rids])}
    records = {r: {} for r in rids}
    for stage, key in ref.STAGES:
        out = np.asarray(ref.stage(conf, stage, params, state, rids,
                                   serve_seed, mode), np.float32)
        for i, r in enumerate(rids):
            records[r][stage] = (state, {key: out}, i)
        state = dict(state, **{key: out})
    served = {r: out[i] for i, r in enumerate(rids)}
    ctl = dict(win, records=records, served=served)
    return harness.check(cell, params, ctl, serve_seed)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", type=int, default=3,
                    help="read the control on the first N seeds")
    args = ap.parse_args()

    import jax

    import harness
    import spec

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    cell = spec.cell(args.workload)
    ctx = harness.build(cell)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        r = harness.start(ctx, cell, seed)
        if i == 0:
            harness.warm_up(r)
        win = harness.serve_window(r["engine"], r["traffic"], 1e-3,
                                   r["recorder"], annotate=False)
        del r["engine"]
        t1 = time.time()
        line = {"seed": seed, "requests": len(win["done"]),
                "serve_s": t1 - t0,
                "program": harness.check(cell, r["params"], win,
                                         r["seeds"]["serve"])}
        if i < args.control:
            line["control"] = control_numbers(cell, r["params"], win,
                                              r["seeds"]["serve"])
        line["check_s"] = time.time() - t1
        print(json.dumps(line), flush=True)
        del r
    ctx["mon"].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
