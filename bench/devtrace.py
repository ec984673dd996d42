"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device operations are the events of the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane; each event's name is the HLO instruction.
A loop appears as one op spanning the ops of its body, which appear too.
A Pallas kernel is a ``tpu_custom_call`` whose name says nothing of the
kernel, so kernels are told apart by the ranks of their operands.  Host spans are the events of the host
plane, among them the ``TraceAnnotation`` spans the harness puts around
each engine step and each stage.  A marker annotation whose wall-clock
start the harness recorded ties the trace's clock to ``time.time()``.
"""

from __future__ import annotations

import collections
import glob
import os
import re

from monitor import union_length

MARKER = "bench/clock"


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - a stat the reader cannot decode
        return {}


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def reduce(pd, marker_wall: float, t0: float, t1: float,
           compile_spans=()) -> dict:
    """Everything the metric readers take from the trace, over the window
    [t0, t1] given on ``time.time()``.

    Returns ``devices`` (per device: busy seconds and op list), ``ops``
    (name, kernel-like text, start, end on the wall clock), ``host`` spans
    and ``gaps`` (idle intervals of device 0, with the host label)."""
    offset = None
    host = []
    devices = collections.OrderedDict()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    text = " ".join(str(v) for v in st.values()
                                    if isinstance(v, str))
                    ops.append((ev.name, text, ev.start_ns, ev.end_ns))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER and offset is None:
                        offset = marker_wall - ev.start_ns * 1e-9
                    host.append((ev.name, ev.start_ns, ev.end_ns))
    if offset is None:
        raise RuntimeError("the trace holds no clock marker")
    wall = lambda ns: ns * 1e-9 + offset
    out_devices = {}
    for name, ops in devices.items():
        ops = [(n, txt, wall(s), wall(e)) for n, txt, s, e in ops
               if t0 <= wall(s) and wall(e) <= t1]
        busy = union_length([(s, e) for _, _, s, e in ops], t0, t1)
        out_devices[name] = {"busy_s": busy, "ops": ops}
    host = [(n, wall(s), wall(e)) for n, s, e in host
            if wall(e) > t0 and wall(s) < t1 and n != MARKER]
    first = next(iter(out_devices.values()), {"ops": []})
    gaps = idle_gaps(first["ops"], t0, t1, host, compile_spans)
    return {"devices": out_devices, "window_s": t1 - t0, "gaps": gaps}


def idle_gaps(ops, t0, t1, host, compile_spans) -> list:
    """Idle intervals between device ops, each labelled by what the host
    did in most of it: ``compile``, else the innermost harness span
    (``stage/<name>``, ``engine.step``) that covers its middle."""
    edges, cur = [], t0
    for _, _, s, e in sorted(ops, key=lambda o: o[2]):
        if s > cur:
            edges.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        edges.append((cur, t1))
    out = []
    for s, e in edges:
        if union_length(compile_spans, s, e) > 0.5 * (e - s):
            label = "compile"
        else:
            mid = 0.5 * (s + e)
            cover = [(he - hs, n) for n, hs, he in host
                     if hs <= mid <= he and n.startswith(("stage/", "engine"))]
            label = min(cover)[1] if cover else "host"
        out.append((label, e - s))
    return out


_OPCODE = re.compile(r" = (.*?) ([a-z][a-z0-9_-]*)\(")
_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
CONTAINERS = ("while", "conditional", "call")


def opcode(hlo: str) -> tuple:
    """(opcode, result shape without layouts) of an HLO instruction."""
    m = _OPCODE.search(hlo)
    if not m:
        return hlo[:80], ""
    return m.group(2), re.sub(r"\{[^}]*\}", "", m.group(1))


def kernel_operands(hlo: str):
    """Operand ranks of a Pallas kernel call, or None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    body = hlo[hlo.index("custom-call(") + len("custom-call("):
               hlo.index("custom_call_target")]
    return tuple(len(d.split(",")) if d else 0
                 for _, d in _SHAPE.findall(body))


def label(hlo: str, families) -> str:
    """A short name for an op: its kernel family, or its opcode, with the
    result shape."""
    code, shape = opcode(hlo)
    ranks = kernel_operands(hlo)
    if ranks is not None:
        code = next((f"{name} kernel" for name, test in families.items()
                     if test(ranks)), "kernel")
    return f"{code} {shape}".strip()


def top_ops(ops, families, n=10) -> list:
    """Device operations with the most time, summed by label; loops and
    calls, which span the ops inside them, are left out."""
    tot = collections.Counter()
    for name, _, s, e in ops:
        if opcode(name)[0] not in CONTAINERS:
            tot[label(name, families)] += e - s
    return [[k, v] for k, v in tot.most_common(n)]


def matching(ops, test) -> list:
    """The Pallas kernel ops whose operand ranks pass ``test``."""
    out = []
    for o in ops:
        ranks = kernel_operands(o[0])
        if ranks is not None and test(ranks):
            out.append(o)
    return out
