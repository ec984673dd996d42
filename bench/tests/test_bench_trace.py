"""The trace reduction: interval arithmetic, idle gaps and their labels,
and the whole reduction on a small trace recorded on a TPU v5e."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
DATA = BENCH / "tests" / "data"

import devtrace  # noqa: E402
from monitor import union_length  # noqa: E402


@pytest.mark.parametrize("spans,t0,t1,want", [
    ([], 0, 10, 0.0),
    ([(1, 2), (3, 5)], 0, 10, 3.0),
    ([(1, 4), (2, 3), (3.5, 6)], 0, 10, 5.0),
    ([(1, 4), (2, 8)], 3, 5, 2.0),
    ([(5, 4)], 0, 10, 0.0),
])
def test_union_length(spans, t0, t1, want):
    assert union_length(spans, t0, t1) == pytest.approx(want)


def test_idle_gaps_are_labelled_by_compile_then_innermost_span():
    ops = [("a", "", 1.0, 2.0), ("b", "", 4.0, 5.0), ("c", "", 5.0, 9.0)]
    host = [("engine.step", 0.0, 10.0), ("stage/vae", 2.0, 3.5)]
    gaps = devtrace.idle_gaps(ops, 0.0, 10.0, host, [(2.1, 3.9)])
    assert gaps == [("engine.step", 1.0), ("compile", 2.0),
                    ("engine.step", 1.0)]


CONV = ('%closed_call.12 = f32[4,32,32,1280]{3,2,1,0:T(8,128)} custom-call('
        'f32[4,34,1,40,1920]{4,3,2,1,0:T(8,128)} %p.1, f32[9,1920,1280]{2,1,0} '
        '%b.2, f32[1,1280]{1,0} %c.3), custom_call_target="tpu_custom_call", '
        'frontend_attributes={kernel_metadata={}}')
ATTN = ('%tpu_custom_call.1 = f32[1,2,256,64]{3,2,1,0} custom-call(f32[1,2,256,64]'
        '{3,2,1,0} %a, f32[1,2,256,64]{3,2,1,0} %b, f32[1,2,256,64]{3,2,1,0} %c), '
        'custom_call_target="tpu_custom_call"')
FUSION = '%fusion.3 = f32[4,4096,320]{2,1,0:T(8,128)} fusion(f32[4,4096,320] %x), kind=kLoop'
LOOP = '%while.10 = (s32[]{:T(128)}, f32[4,64,64,4]{3,2,1,0}) while((s32[], f32[4,64,64,4]) %t)'


def test_kernels_are_told_apart_by_operand_ranks():
    from roofline import FAMILIES

    assert devtrace.kernel_operands(CONV) == (5, 3, 2)
    assert devtrace.kernel_operands(ATTN) == (4, 4, 4)
    assert devtrace.kernel_operands(FUSION) is None
    assert devtrace.label(CONV, FAMILIES) == "conv kernel f32[4,32,32,1280]"
    assert devtrace.label(FUSION, FAMILIES) == "fusion f32[4,4096,320]"
    ops = [(CONV, "", 0, 1), (ATTN, "", 1, 4), (FUSION, "", 4, 6),
           (LOOP, "", 0, 6), (CONV, "", 6, 6.5)]
    assert devtrace.matching(ops, FAMILIES["conv"]) == [ops[0], ops[4]]
    assert devtrace.matching(ops, FAMILIES["attention"]) == [ops[1]]
    assert devtrace.top_ops(ops, FAMILIES, 2) == [
        ["attention kernel f32[1,2,256,64]", 3], ["fusion f32[4,4096,320]", 2]]


def test_recorded_v5e_trace():
    from roofline import FAMILIES

    meta = json.loads((DATA / "small_v5e.json").read_text())
    pd = devtrace.load(str(DATA))
    t0, t1 = meta["marker_wall"], meta["end_wall"]
    r = devtrace.reduce(pd, t0, t0, t1)
    assert list(r["devices"]) == ["/device:TPU:0"]
    dev = r["devices"]["/device:TPU:0"]
    assert 0 < dev["busy_s"] < r["window_s"] == pytest.approx(t1 - t0)
    assert all(t0 <= s <= e <= t1 for _, _, s, e in dev["ops"])
    for kind, n in meta["kernels"].items():
        assert len(devtrace.matching(dev["ops"], FAMILIES[kind])) == n
    labels = {g[0] for g in r["gaps"]}
    assert {"engine.step", "stage/vae"} <= labels <= {"engine.step",
                                                      "stage/vae", "host"}
    assert sum(g[1] for g in r["gaps"]) == pytest.approx(
        r["window_s"] - dev["busy_s"], rel=1e-6)
