#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload sd512-c4 --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic, limits and metrics are read from
``BENCHMARK.json`` and the files under ``bench/``.  The run makes weights
and traffic from ``--seed``, warms up, offers the traffic for
``--seconds``, serves everything offered, checks the served outputs
against the plain reference, and prints one JSON line last on stdout:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
(read from a profiler trace of the window) with ``--trace 1``.

Without a TPU, or with fewer chips than the cell needs, it prints no
result and exits non-zero.
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import spec

    cell = spec.cell(args.workload)
    import jax

    import harness
    import repro.serving.engine  # noqa: F401  (the system under test)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU: JAX sees {len(devices)} "
              f"{devices[0].platform} device(s); cell {cell['name']} needs "
              f"{cell['chips']} TPU chip(s)", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    out = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
