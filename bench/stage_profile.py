#!/usr/bin/env python3
"""Device and compile seconds of one cell's window, stage by stage, read from
the program's own spans and counters.

    python3 bench/stage_profile.py --workload sd512-c4 --seed 7 --seed 8 --seconds 51

For each seed, the set-up, warm-up and traced window of ``bench/run.py
--trace 1`` (the same harness functions and profiler options), without the
reference check.  Then:

* device seconds by stage: each device op put down to the
  ``serve/stage/<name>`` span that launched its program
  (``attribution.attribute``), beside ``busy_s`` as ``devtrace.reduce``
  counts it;
* compiles by stage: the window's change in the engine's
  ``compiles/<stage>`` and ``compile_s/<stage>`` counters
  (``repro.telemetry.compiles``), beside the window's counts from
  ``monitor.Monitor``;
* the readings of ``metrics/window_compiles.py``, ``denoise_step_ms.py``
  and ``vae_image_ms.py`` on these.

One JSON line per seed on stdout.  ``--reduced`` runs the cell at the
program's reduced configuration (on the CPU as well: a rehearsal, with no
device planes).  ``--keep PREFIX`` keeps the trace of the last seed as
``PREFIX.xplane.pb`` with ``PREFIX.json`` (clock marker, window, readings).
Without a TPU and without ``--reduced`` it exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

READERS = ("window_compiles", "denoise_step_ms", "vae_image_ms")


def compile_delta(before: dict, after: dict) -> dict:
    """Per-label change of ``stage_compiles`` counts between two reads."""
    out = {}
    for label, v in after.items():
        b = before.get(label, {"compiles": 0, "compile_s": 0.0})
        d = {k: v[k] - b[k] for k in ("compiles", "compile_s")}
        if d["compiles"] or d["compile_s"]:
            out[label] = d
    return out


def profile(ctx: dict, cell: dict, seed: int, seconds: float,
            keep: str | None) -> dict:
    import jax

    import harness
    import spec
    from attribution import attribute
    from devtrace import MARKER, load, reduce
    from repro.telemetry.compiles import stage_compiles

    mon = ctx["mon"]
    r = harness.start(ctx, cell, seed, annotate=True)
    harness.warm_up(r)
    engine = r["engine"]
    warm, warm_prog = mon.snapshot(), stage_compiles(engine.metrics)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(MARKER):
        marker = time.time()
    w0 = time.time()
    win = harness.serve_window(engine, r["traffic"], seconds, r["recorder"],
                               annotate=True)
    span = max(win["done"].values(), default=math.nan)
    w1 = w0 + span
    jax.profiler.stop_trace()
    mon_window = {k: v - warm.get(k, 0) for k, v in mon.snapshot().items()}
    prog = compile_delta(warm_prog, stage_compiles(engine.metrics))
    del r, engine
    gc.collect()

    t_red = time.time()
    pd = load(trace_dir)
    reduced = reduce(pd, marker, w0, w1, mon.spans)
    att = attribute(pd, marker, w0, w1)
    red_s = time.time() - t_red
    conf = cell["config"]["config"]
    run_info = {"config": conf, "pods": win["pods"],
                "completed": len(win["done"]), "chips": cell["chips"],
                "trace": reduced, "stage_compiles": prog, "stage_busy": att}
    readings = {m: spec.load_module("metrics", m).read(run_info)
                for m in READERS}
    devices = {
        name: {"busy_s": reduced["devices"][name]["busy_s"],
               "attributed_busy_s": a["busy_s"],
               "stages": {k: v["busy_s"] for k, v in a["stages"].items()},
               "unattributed_s": a["unattributed"]["busy_s"]}
        for name, a in att.items()}
    out = {"seed": seed, "window_s": span, "pods": win["pods"],
           "requests": len(win["done"]),
           "monitor": {"compiles": mon_window.get("compiles", 0),
                       "compile_s": mon.compile_seconds(w0, w1)},
           "stage_compiles": prog, "devices": devices,
           "readings": readings, "reduction_s": red_s}
    for name, d in devices.items():
        for stage, s in sorted(d["stages"].items()):
            harness.log(f"{name} {stage}: device {s:.4f} s")
        harness.log(f"{name} unattributed: device {d['unattributed_s']:.4f} "
                    f"s of busy {d['busy_s']:.4f} s")
    for label, c in sorted(prog.items()):
        harness.log(f"compiles {label}: {c['compiles']} in "
                    f"{c['compile_s']:.4f} s")
    harness.log(f"window {span:.4f} s | monitor {out['monitor']} | "
                f"readings {readings}")
    if keep:
        [pb] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)
        shutil.copy(pb, keep + ".xplane.pb")
        meta = dict(out, marker_wall=marker, start_wall=w0, end_wall=w1,
                    cell=cell["name"], config=conf["name"],
                    device=jax.devices()[0].device_kind)
        Path(keep + ".json").write_text(json.dumps(meta, indent=1) + "\n")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def reduced_cell(cell: dict):
    """The cell at the program's reduced configuration: (cell, config)."""
    import spec
    from repro.configs import get_config
    from repro.workload import workload_for

    pc = workload_for(get_config(cell["config"]["arch"])).reduced()
    cell = dict(cell, config=dict(cell["config"], config=spec.plain(pc)))
    return cell, pc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--keep", default=None)
    args = ap.parse_args()

    import spec

    cell = spec.cell(args.workload)
    import jax

    import harness
    import repro.configs.suite  # noqa: F401  (registers the paper suite)

    if jax.devices()[0].platform != "tpu" and not args.reduced:
        print(f"stage_profile: no TPU; cell {cell['name']} runs on "
              f"{cell['chips']} TPU chip(s)", file=sys.stderr)
        return 2
    program_cfg = None
    if args.reduced:
        cell, program_cfg = reduced_cell(cell)
    ctx = harness.build(cell, program_cfg)
    try:
        for i, seed in enumerate(args.seed):
            last = i == len(args.seed) - 1
            out = profile(ctx, cell, seed, args.seconds,
                          args.keep if last else None)
            print(json.dumps(out), flush=True)
    finally:
        ctx["mon"].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
