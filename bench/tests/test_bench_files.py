"""Every file BENCHMARK.json names loads, with the names and units the
benchmark's contract allows, and each configuration file holds the
configuration the program runs."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import spec  # noqa: E402

B = spec.benchmark()
METRICS = B["end_to_end"] + B["per_layer"]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w for w in B["command"])


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_units_and_reader(m):
    assert spec.NAME.match(m["name"]) and spec.UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(spec.load_module("metrics", m["name"]).read)
    if m in B["per_layer"]:
        assert m["moves"] in {e["name"] for e in B["end_to_end"]}
        cells = {w["name"] for w in B["workloads"]}
        assert set(m.get("workloads", cells)) <= cells
    else:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("w", B["workloads"], ids=[w["name"] for w in B["workloads"]])
def test_cell_files_load(w):
    assert spec.NAME.match(w["name"]) and spec.NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    cell = spec.cell(w["name"])
    t = cell["traffic"]
    assert t["loop"] == "closed" and t["pod_size"] >= 1
    assert set(cell["limits"]["limits"]) == {"handoff_err"} | {
        f"{s}_err" for s, _ in spec.load_module(
            "reference", cell["config"]["config"]["family"]).STAGES}
    assert {m["name"] for m in cell["metrics"]["end_to_end"]} >= {"setup_s"}
    assert cell["metrics"]["per_layer"]


@pytest.mark.parametrize("c", B["configs"], ids=[c["name"] for c in B["configs"]])
def test_config_entry_names_its_file(c):
    assert c["file"] == f"bench/configs/{c['name']}.json"
    f = spec.load_json(spec.ROOT / c["file"])
    assert f["reduced"] == c["reduced"] and f["source"] == c["source"]
    assert all(spec.NAME.match(k) for k in c["reduced"])


CONFIGS = sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_is_the_config_as_run(name):
    import repro.configs.suite  # noqa: F401
    from repro.configs import get_config

    f = spec.load_json(spec.BENCH / "configs" / f"{name}.json")
    assert spec.differences(f["config"], spec.plain(get_config(f["arch"]))) == []
    assert f["matmul_precision"] == "highest"
    # counts and reference exist for the configuration; limits exist where
    # a cell uses it, and without them the configuration is refused
    spec.load_module("counts", f["config"]["family"])
    ref = spec.load_module("reference", f["config"]["family"])
    used = {w["config"] for w in B["workloads"]}
    if name in used or (spec.BENCH / "limits" / f"{name}.json").exists():
        assert set(spec.limits(name)["limits"]) == {"handoff_err"} | {
            f"{s}_err" for s, _ in ref.STAGES}
    else:
        with pytest.raises(SystemExit, match="no correctness limits"):
            spec.limits(name)


def test_differences_names_a_changed_width():
    import repro.configs.suite  # noqa: F401
    from repro.configs import get_config

    f = spec.load_json(spec.ROOT / "bench/configs/stable-diffusion.json")
    f["config"]["unet"]["model_channels"] = 256
    diff = spec.differences(f["config"], spec.plain(get_config(f["arch"])))
    assert diff == ["config.unet.model_channels: file 256, program 320"]


def test_peaks_table():
    peaks = spec.load_json(spec.BENCH / "peaks.json")
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
