"""One run of one cell: set-up, a measured window, the check, the metrics.

The system under test is the program's ``ServeEngine``, built the way the
program's serve launcher builds it (configuration -> workload ->
``ServeConfig(route="auto", impl="auto")``); on a TPU every stage then runs
its Pallas kernels.  The harness gives it weights made from the seed,
offers it the cell's traffic on its own wall clock, and never reads the
engine's tick clock.  Requests offered inside the window are all awaited:
rates and latencies cover every one of them, up to the last completion.
"""

from __future__ import annotations

import contextlib
import gc
import math
import shutil
import sys
import tempfile
import time

import numpy as np

import spec
from traffic import Traffic

WARMUP_RID = 1 << 30


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def seeds(seed: int) -> dict:
    """Independent 31-bit seeds for weights, the server and the traffic."""
    w, s, t, _ = np.random.SeedSequence(seed).generate_state(4)
    return {"weights": int(w) >> 1, "serve": int(s) >> 1,
            "traffic": int(t) >> 1}


def device_line(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


class Recorder:
    """Wraps the workload's ``run_stage`` to keep each served stage's input
    and output state (references only, nothing is copied in the window)
    and, in a traced run, to name the stage in the trace."""

    def __init__(self, workload, annotate: bool):
        self.calls, self.on = [], True
        orig = workload.run_stage

        def run_stage(params, stage, state, key, **kw):
            import jax

            ctx = (jax.profiler.TraceAnnotation(f"stage/{stage.name}")
                   if annotate else contextlib.nullcontext())
            with ctx:
                out = orig(params, stage, state, key, **kw)
            if self.on:
                self.calls.append((stage.name, state, out))
            return out

        workload.run_stage = run_stage

    def take(self, rids) -> dict:
        """The calls since the last ``take``, by request id (the pod lists
        its requests in order): ``{rid: {stage: (state_in, state_out,
        row)}}``.  Nothing is sliced or copied here, inside the window."""
        out = {r: {} for r in rids}
        for name, sin, sout in self.calls:
            for i, r in enumerate(rids):
                out[r][name] = (sin, sout, i)
        self.calls = []
        return out


def serve_window(engine, traffic: Traffic, seconds: float, recorder,
                 annotate: bool) -> dict:
    """Offer the traffic for ``seconds``, then serve what was offered to
    the end.  Times are ``perf_counter`` seconds from the window's start."""
    import jax

    t0 = time.perf_counter()
    due, prompts, done_at, records, served, pods = {}, {}, {}, {}, {}, []

    def submit(at):
        rid = len(due)
        prompts[rid] = traffic.prompt(rid)
        engine.submit(rid, prompts[rid], 0, arrival_tick=0)
        due[rid] = at

    # closed loop: each client sends its next request when its last returns
    for _ in range(traffic.clients):
        submit(0.0)
    while engine.pending():
        g0 = engine.stats.get("generate_s", 0.0)
        ctx = (jax.profiler.TraceAnnotation("engine.step") if annotate
               else contextlib.nullcontext())
        with ctx:
            done = engine.step()
        t = time.perf_counter() - t0
        if done:
            rids = [r for r, _ in done]
            pods.append({"requests": len(done), "end_s": t,
                         "generate_s": engine.stats.get("generate_s", 0.0) - g0})
            records.update(recorder.take(rids))
            for r, out in done:
                done_at[r], served[r] = t, out
                if t < seconds:
                    submit(t)
    return {"due": due, "done": done_at, "prompts": prompts,
            "records": records, "served": served, "pods": pods}


def memory(jax, chips: int) -> dict:
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    limit = min(s.get("bytes_limit", 0) for s in stats)
    return {"peak_bytes": peak, "bytes_limit": limit}


def build(cell: dict, program_cfg=None) -> dict:
    """Imports, the compile cache, and the program's configuration, checked
    against the configuration file."""
    import jax

    from monitor import Monitor

    mon = Monitor()
    # the compile cache lives at a fixed place in the checkout, holds every
    # program however short its compile, and evicts nothing, so that only
    # the first run of a cell in a checkout compiles
    cache_dir = str(spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import repro.configs.suite  # noqa: F401  (registers the paper suite)
    from repro.configs import get_config
    from repro.workload import workload_for

    conf = cell["config"]
    # the arithmetic the configuration states: float32 matmuls at full
    # precision, which a TPU gives only when asked
    jax.config.update("jax_default_matmul_precision", conf["matmul_precision"])
    pcfg = program_cfg or get_config(conf["arch"])
    diff = spec.differences(conf["config"], spec.plain(pcfg))
    if diff:
        mon.close()
        raise SystemExit("the configuration file does not hold the "
                         "configuration as run: " + "; ".join(diff))
    dev = device_line(jax)
    log(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}) | "
        f"compile cache {cache_dir} | matmul precision "
        f"{conf['matmul_precision']}")
    workload = workload_for(pcfg)
    return {"mon": mon, "device": dev, "workload": workload,
            "shapes": jax.eval_shape(workload.model.init,
                                     jax.random.PRNGKey(0))}


def start(ctx: dict, cell: dict, seed: int, annotate: bool = False,
          stage_fault=None) -> dict:
    """Weights and traffic from ``seed``, and an engine serving them."""
    import jax

    import weights
    from repro.serving.engine import ServeConfig, ServeEngine

    s = seeds(seed)
    workload = ctx["workload"]
    params = jax.block_until_ready(weights.make(ctx["shapes"], s["weights"]))
    traffic = Traffic(cell["traffic"], s["traffic"], workload.prompt_vocab,
                      workload.max_prompt_len)
    engine = ServeEngine(workload, params, ServeConfig(
        route="auto", impl="auto", seed=s["serve"],
        pod_size=traffic.pod_size, max_batch=traffic.pod_size))
    if stage_fault is not None:
        stage_fault(workload)
    rec = Recorder(workload, annotate=annotate)
    return {"seeds": s, "params": params, "traffic": traffic,
            "engine": engine, "recorder": rec}


def warm_up(run: dict) -> None:
    """Serve one pod of the shape the window serves, outside the window."""
    engine, traffic, rec = run["engine"], run["traffic"], run["recorder"]
    rec.on = False
    batch = min(traffic.pod_size, traffic.clients)
    for i in range(batch):
        engine.submit(WARMUP_RID + i, traffic.prompt(WARMUP_RID + i), 0,
                      arrival_tick=0)
    while engine.pending():
        engine.step()
    rec.on = True


def check(cell: dict, params, win: dict, serve_seed: int,
          mode: str = "float32") -> dict:
    """The numbers compared, for every request that ``win`` served,
    against the reference computed in ``mode`` (see ``reference.nn``), in
    blocks of one pod's rows."""
    from check import numbers

    conf = cell["config"]["config"]
    ref = spec.load_module("reference", conf["family"])
    return numbers(ref, conf, params, win["records"], win["served"],
                   win["prompts"], serve_seed, cell["traffic"]["pod_size"],
                   mode)


def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        program_cfg=None, stage_fault=None, peaks=None) -> dict:
    """The result dict of one run.  ``program_cfg`` replaces the program's
    registered configuration and ``peaks`` the device's published peaks
    (tests run a reduced configuration on the CPU); ``stage_fault`` wraps
    ``run_stage`` underneath the recorder (tests plant faults)."""
    ctx = build(cell, program_cfg)
    try:
        return _run(ctx, cell, seed, seconds, trace, t_start, stage_fault,
                    peaks)
    finally:
        ctx["mon"].close()


def _run(ctx, cell, seed, seconds, trace, t_start, stage_fault, peaks):
    import jax

    from check import verdict
    from devtrace import MARKER

    mon, dev = ctx["mon"], ctx["device"]
    r = start(ctx, cell, seed, annotate=trace, stage_fault=stage_fault)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(r["params"]))
    log(f"weights: {nbytes} bytes, ready at {time.time() - t_start:.2f} s, "
        f"memory {memory(jax, cell['chips'])}")
    warm_up(r)
    warm = mon.snapshot()
    log(f"warm-up: {r['traffic'].pod_size} requests a pod, done at "
        f"{time.time() - t_start:.2f} s, cache {warm}, memory "
        f"{memory(jax, cell['chips'])}")

    trace_dir = marker = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        # device ops and the harness's own annotations; no Python call
        # tracing and no HLO protos, which would slow and swell the trace
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(MARKER):
            marker = time.time()
    w0 = time.time()
    setup_s = w0 - t_start
    win = serve_window(r["engine"], r["traffic"], seconds, r["recorder"],
                       annotate=trace)
    span = max(win["done"].values(), default=math.nan)
    w1 = w0 + span
    if trace:
        jax.profiler.stop_trace()
    mem = memory(jax, cell["chips"])
    window_counts = {k: v - warm.get(k, 0) for k, v in mon.snapshot().items()}
    for i, p in enumerate(win["pods"]):
        log(f"pod {i}: {p['requests']} requests, generate_s "
            f"{p['generate_s']:.4f}, done at {p['end_s']:.4f} s")
    log(f"window: {len(win['done'])}/{len(win['due'])} requests done, "
        f"last at {span:.4f} s | cache in window {window_counts}")

    # the check runs after the window, with the engine gone
    del r["engine"]
    gc.collect()
    t_ref = time.time()
    nums = check(cell, r["params"], win, r["seeds"]["serve"])
    correct, table = verdict(nums, cell["limits"])
    log(f"reference check: {time.time() - t_ref:.2f} s")
    attempted, completed = len(win["due"]), len(win["done"])
    correct = correct and completed == attempted

    reduced = None
    if trace:
        from devtrace import load, reduce

        t_red = time.time()
        reduced = reduce(load(trace_dir), marker, w0, w1, mon.spans)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduction: {time.time() - t_red:.2f} s")

    conf = cell["config"]["config"]
    run_info = {
        "cell": cell, "config": conf, "seconds": seconds,
        "setup_s": setup_s, "span_s": span, "window": (w0, w1),
        "latencies": [win["done"][q] - win["due"][q] for q in win["done"]],
        "completed": completed, "pods": win["pods"],
        "prompt_len": r["traffic"].padded_len,
        "compile_s": mon.compile_seconds(w0, w1), "memory": mem,
        "peaks": peaks or peaks_of(dev["kind"]), "chips": cell["chips"],
        "trace": reduced,
        "stage_calls": spec.load_module("counts", conf["family"]).stage_calls,
    }
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell["metrics"][kind]:
        v = spec.load_module("metrics", m["name"]).read(run_info)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(dev, count=cell["chips"], memory_peak_bytes=mem["peak_bytes"])
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": attempted - completed, "metrics": metrics,
           "device": device}
    if trace:
        from devtrace import top_ops
        from roofline import FAMILIES

        devs = list(reduced["devices"].values())[:cell["chips"]]
        device["busy_s"] = sum(d["busy_s"] for d in devs) / max(len(devs), 1)
        device["window_s"] = reduced["window_s"]
        gaps = sorted(reduced["gaps"], key=lambda g: -g[1])[:10]
        out["breakdown"] = {
            "device_ops": top_ops(devs[0]["ops"], FAMILIES) if devs else [],
            "idle_gaps": [list(g) for g in gaps]}
    for k, v in table.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    out["check"] = table
    return out


def peaks_of(kind: str) -> dict:
    table = spec.load_json(spec.BENCH / "peaks.json")
    if kind not in table:
        raise SystemExit(f"no published peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]
