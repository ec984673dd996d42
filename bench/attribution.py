"""Device time by program stage, from a profiler trace.

Each execution of a program on a device is an event of the ``XLA Modules``
line of a ``/device:TPU:<n>`` plane, with a ``run_id`` and a flow id
``_c``.  The host enqueue that launched it, ``DoEnqueueProgram``, carries
the same ``run_id`` and the matching ``_p``.  The enqueue runs inside a
``tpu::System::Execute=>IssueSequencedEvent``, whose flow ``_c`` leads back
to the ``tpu::System::Execute`` of the thread that launched the program:
mostly the Python thread itself, and for some programs a PJRT worker thread
(``pjrt-tpu-tasks``) a little later.  The launch time is that producer's
start (the enqueue's own start where it has none), and the launch belongs
to the innermost host annotation whose name starts with ``prefix`` open at
that time: ``serve/stage/<name>`` for the program's own stage spans.  Each
device op (``XLA Ops``) inherits the stage of the program execution that
encloses it, and a stage's device seconds are the union of its ops'
intervals, as ``devtrace.reduce`` counts ``busy_s``.  No device op is
looked up by time alone: an op that ran after its stage span closed still
belongs to the stage that launched it.
"""

from __future__ import annotations

import bisect
import collections

from devtrace import MARKER, _stats
from monitor import union_length

SEQUENCED = "tpu::System::Execute=>IssueSequencedEvent"
EXECUTE = "tpu::System::Execute"
ENQUEUE = "DoEnqueueProgram"


def launches(pd, prefix: str):
    """(marker start ns, stage spans, launch ns by run_id, launch ns by
    flow id) from the host planes."""
    marker, spans = None, []
    producers, enqueues = {}, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            seq = None  # the sequenced-event span last opened on this line
            for ev in line.events:
                name = ev.name
                if name.startswith(prefix):
                    spans.append((ev.start_ns, ev.end_ns, name[len(prefix):]))
                elif name == MARKER and marker is None:
                    marker = ev.start_ns
                elif name == EXECUTE:
                    flow = _stats(ev).get("_p")
                    if flow is not None:
                        producers[flow] = ev.start_ns
                elif name == SEQUENCED:
                    seq = (ev.start_ns, ev.end_ns, _stats(ev).get("_c"))
                elif name == ENQUEUE:
                    st = _stats(ev)
                    flow = (seq[2] if seq and seq[0] <= ev.start_ns
                            <= seq[1] else None)
                    enqueues.append((st.get("run_id"), st.get("_p"), flow,
                                     ev.start_ns))
    by_run, by_flow = {}, {}
    for run_id, out_flow, in_flow, start in enqueues:
        at = producers.get(in_flow, start)
        if run_id is not None:
            by_run[run_id] = at
        if out_flow is not None:
            by_flow[out_flow] = at
    spans.sort()
    return marker, spans, by_run, by_flow


def stage_at(spans, starts, t):
    """The innermost span open at ``t``: of those open then, the one that
    started last."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i][1] >= t:
            return spans[i][2]
    return None


def attribute(pd, marker_wall: float, t0: float, t1: float,
              prefix: str = "serve/stage/") -> dict:
    """Device seconds of the window [t0, t1] (on ``time.time()``, tied to the
    trace by the ``MARKER`` annotation that started at ``marker_wall``), by
    the stage that launched them.

    Returns, per device plane: ``busy_s`` (as ``devtrace.reduce``),
    ``stages`` (``{stage: {"busy_s", "ops"}}``) and ``unattributed``
    (``{"busy_s", "ops"}``: ops whose program was launched outside every
    stage span, or whose launch the trace does not hold).  Ops are
    ``(hlo, "", start, end)`` on the wall clock, as in ``reduce``."""
    marker, spans, by_run, by_flow = launches(pd, prefix)
    if marker is None:
        raise RuntimeError("the trace holds no clock marker")
    offset = marker_wall - marker * 1e-9
    wall = lambda ns: ns * 1e-9 + offset
    starts = [s for s, _, _ in spans]
    out = {}
    for plane in pd.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    st = _stats(ev)
                    at = by_run.get(st.get("run_id"), by_flow.get(st.get("_c")))
                    stage = None if at is None else stage_at(spans, starts, at)
                    modules.append((ev.start_ns, ev.end_ns, stage))
            elif line.name == "XLA Ops":
                ops += [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
        modules.sort()
        mod_starts = [m[0] for m in modules]
        groups = collections.defaultdict(list)
        for name, s, e in ops:
            if not (t0 <= wall(s) and wall(e) <= t1):
                continue
            i = bisect.bisect_right(mod_starts, s) - 1
            # the op starts inside its module (its end, rounded, may not)
            stage = modules[i][2] if i >= 0 and modules[i][1] >= s else None
            groups[stage].append((name, "", wall(s), wall(e)))
        busy = lambda o: union_length([(s, e) for _, _, s, e in o], t0, t1)
        rest = groups.pop(None, [])
        out[plane.name] = {
            "busy_s": busy([o for g in groups.values() for o in g] + rest),
            "stages": {k: {"busy_s": busy(v), "ops": v}
                       for k, v in sorted(groups.items())},
            "unattributed": {"busy_s": busy(rest), "ops": rest},
        }
    return out


def stage_seconds(run: dict, stage: str) -> float | None:
    """Device seconds launched by ``stage`` in the run's window, averaged
    over the cell's chips, from ``run["stage_busy"]`` (what ``attribute``
    returns); None where there is no trace or the stage launched nothing."""
    att = run.get("stage_busy")
    if not att:
        return None
    devs = list(att.values())[:run["chips"]]
    if not all(stage in d["stages"] for d in devs):
        return None
    return sum(d["stages"][stage]["busy_s"] for d in devs) / len(devs)
