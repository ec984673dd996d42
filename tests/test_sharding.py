"""Sharded serving (ISSUE 10): mesh-aware params + activation sharding on
the canonical execution path.

Single-device-safe tests cover the rule tables, ``parse_mesh`` /
``ensure_host_device_count``, and the n<k degenerate slice assignment;
everything touching a real multi-device mesh is gated on
``jax.device_count()`` and runs in the host-mesh CI lane
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

import os
import types
import warnings

import jax
import numpy as np
import pytest

from repro.configs.tiny import TINY_TTI_CASCADE
from repro.launch.mesh import (
    ensure_host_device_count,
    make_debug_mesh,
    parse_mesh,
)
from repro.parallel.sharding import (
    REPLICATION_FALLBACKS,
    SERVE_RULES,
    SERVE_TP_RULES,
    concat_unsharded,
    shard_report,
    spec_for,
)
from repro.serving.engine import ServeConfig, ServeEngine
from repro.workload import workload_for

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


# ---------------------------------------------------------------------------
# Rule tables + mesh spec parsing (single-device)
# ---------------------------------------------------------------------------


def test_serve_tp_rules_extend_serve_rules_with_conv_tp():
    """SERVE_TP_RULES is SERVE_RULES plus channel-parallel conv TP — the
    rule that shards the attention-free SR UNets."""
    assert SERVE_TP_RULES["conv_out"] == "model"
    for k, v in SERVE_RULES.items():
        if k != "conv_out":
            assert SERVE_TP_RULES[k] == v


def test_parse_mesh_accepts_dxm_and_rejects_garbage():
    assert parse_mesh("4x2") == (4, 2)
    assert parse_mesh("1X8") == (1, 8)
    assert parse_mesh(" 2 x 4 ") == (2, 4)
    for bad in ("", "4", "4x", "x2", "4x2x1", "0x2", "4x-1", "axb"):
        with pytest.raises(ValueError):
            parse_mesh(bad)


def test_ensure_host_device_count_respects_existing_env(monkeypatch):
    """An operator-set --xla_force_host_platform_device_count wins; absent
    one, the helper appends the flag (the dryrun/hillclimb default)."""
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=16")
    assert ensure_host_device_count(512) == 16
    assert "=16" in os.environ["XLA_FLAGS"]

    monkeypatch.setenv("XLA_FLAGS", "--some_other_flag")
    assert ensure_host_device_count(512) == 512
    assert "--some_other_flag" in os.environ["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=512" in os.environ["XLA_FLAGS"]

    monkeypatch.delenv("XLA_FLAGS")
    assert ensure_host_device_count(8) == 8
    assert os.environ["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"

    # respect_env=False: the requested count overrides an existing flag
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=16")
    assert ensure_host_device_count(512, respect_env=False) == 512
    assert "=512" in os.environ["XLA_FLAGS"]


def _stage(name, demand, steps=1, seq_len=256):
    return types.SimpleNamespace(
        name=name, demand=demand, steps=steps, seq_len=seq_len)


def test_stage_mesh_slices_share_full_mesh_when_fewer_devices_than_stages():
    from repro.parallel.mesh_exec import stage_mesh_slices

    mesh = make_debug_mesh(1, 1)
    stages = [_stage("a", [1.0]), _stage("b", [2.0]), _stage("c", [3.0])]
    slices = stage_mesh_slices(stages, mesh)
    assert len(slices) == 3
    assert all(s is mesh for s in slices)


# ---------------------------------------------------------------------------
# Multi-device: fallback accounting, TP coverage, slice assignment, serving
# ---------------------------------------------------------------------------


@needs_mesh
def test_spec_for_replication_fallback_warns_once_and_counts():
    """A dim that doesn't divide its mesh axis replicates with ONE warning
    per (axis, dim, mesh-size) signature and a telemetry Counter tick —
    never a silent fallback."""
    mesh = make_debug_mesh(4, 2)
    before = REPLICATION_FALLBACKS.value
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        spec = spec_for(("mlp",), (31,), mesh)  # 31 % 2 != 0 -> replicate
    assert tuple(spec) == (None,)
    assert REPLICATION_FALLBACKS.value == before + 1
    ours = [x for x in w if "replicating" in str(x.message)]
    assert len(ours) == 1
    # same signature again: counted, not re-warned
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        spec_for(("mlp",), (31,), mesh)
    assert REPLICATION_FALLBACKS.value == before + 2
    assert not [x for x in w2 if "replicating" in str(x.message)]


@needs_mesh
def test_shard_report_accounts_every_param_byte():
    mesh = make_debug_mesh(4, 2)
    wl = workload_for(TINY_TTI_CASCADE)
    params = wl.init(jax.random.PRNGKey(0))
    rep = shard_report(params, wl.model.specs(), mesh, SERVE_TP_RULES)
    assert rep["sharded_bytes"] + rep["replicated_bytes"] == rep["total_bytes"]
    leaves = jax.tree.leaves(params)
    assert rep["total_bytes"] == sum(x.size * x.dtype.itemsize for x in leaves)
    # conv TP puts the bulk of the UNet on the model axis
    assert rep["tp_coverage"] > 0.5
    assert rep["tp_coverage"] == rep["sharded_bytes"] / rep["total_bytes"]


@needs_mesh
def test_stage_mesh_slices_partition_all_devices_heavy_stages_tp():
    from repro.parallel.mesh_exec import stage_mesh_slices

    mesh = make_debug_mesh(4, 2)
    stages = [_stage("text_encoder", [0.05]),
              _stage("denoise", [1.0]),
              _stage("sr0", [4.0])]
    slices = stage_mesh_slices(stages, mesh)
    assert len(slices) == 3
    assert all(s.devices.size >= 1 for s in slices)
    # a partition: every device used exactly once
    ids = [d.id for s in slices for d in s.devices.flat]
    assert sorted(ids) == [d.id for d in mesh.devices.reshape(-1)]
    # the heaviest stage is model-parallel, the lightest data-parallel
    assert slices[2].shape["model"] == slices[2].devices.size
    assert slices[0].shape["model"] == 1
    # demand-proportional: sr0 gets the most devices
    assert slices[2].devices.size >= slices[1].devices.size >= 1


@needs_mesh
def test_concat_unsharded_matches_unsharded_concat():
    """The workaround for XLA's sharded-axis concatenate miscompile: with
    operands (and output) pinned unsharded on the concat axis the result is
    bit-identical to the single-device concat.  The raw concat is NOT
    asserted wrong here — a fixed XLA would make that xfail flap — only
    that the routed path is right."""
    mesh = make_debug_mesh(4, 2)
    rng = np.random.default_rng(0)
    a = jax.numpy.asarray(rng.standard_normal((2, 4, 4, 16)).astype(np.float32))
    b = jax.numpy.asarray(rng.standard_normal((2, 4, 4, 16)).astype(np.float32))
    ref = np.asarray(jax.numpy.concatenate([a, b], axis=-1))
    from jax.sharding import NamedSharding, PartitionSpec as P

    b_sh = jax.device_put(b, NamedSharding(mesh, P(None, None, None, "model")))
    with mesh:
        out = np.asarray(concat_unsharded([a, b_sh], axis=-1))
    np.testing.assert_array_equal(ref, out)


@needs_mesh
def test_engine_mesh_stats_and_pod_route_parity():
    """Serving over a (4,2) mesh reports geometry + TP coverage in
    engine.stats['mesh'] and matches the single-device engine to float
    accumulation tolerance."""
    wl = workload_for(TINY_TTI_CASCADE)
    params = wl.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, wl.prompt_vocab, size=8) for _ in range(4)]

    def run(mesh):
        eng = ServeEngine(wl, params,
                          ServeConfig(max_batch=2, buckets=(8,),
                                      queue_capacity=2, mesh=mesh))
        for rid, p in enumerate(prompts):
            eng.submit(rid, p)
        return {r: np.asarray(o) for r, o in eng.run().items()}, eng

    ref, _ = run(None)
    out, eng = run(make_debug_mesh(4, 2))
    ms = eng.stats["mesh"]
    assert ms["axes"] == {"data": 4, "model": 2}
    assert ms["devices"] == 8
    assert 0.0 < ms["params"]["tp_coverage"] <= 1.0
    assert ms["params"]["sharded_bytes"] + ms["params"]["replicated_bytes"] \
        == ms["params"]["total_bytes"]
    scale = max(float(np.max(np.abs(ref[r]))) for r in ref)
    for r in ref:
        # fp32 reduction-order tolerance; real sharding bugs show up at
        # O(scale) (the concatenate miscompile measured ~0.5 * scale)
        assert float(np.max(np.abs(ref[r] - out[r]))) <= 1e-5 * scale


@needs_mesh
def test_cascade_route_stage_slices_and_reshard_accounting():
    """Cascade serving over a mesh: per-stage device slices partition the
    mesh, cross-slice handoffs are counted, outputs match single-device."""
    wl = workload_for(TINY_TTI_CASCADE)
    params = wl.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, wl.prompt_vocab, size=8) for _ in range(4)]

    def run(mesh):
        eng = ServeEngine(wl, params,
                          ServeConfig(max_batch=2, buckets=(8,),
                                      route="cascade", queue_capacity=2,
                                      mesh=mesh))
        for rid, p in enumerate(prompts):
            eng.submit(rid, p)
        return {r: np.asarray(o) for r, o in eng.run().items()}, eng

    ref, _ = run(None)
    out, eng = run(make_debug_mesh(4, 2))
    cm = eng.stats["cascade"]["mesh"]
    assert sum(cm["stage_devices"].values()) == 8
    assert cm["reshard_events"] > 0 and cm["reshard_bytes"] > 0
    scale = max(float(np.max(np.abs(ref[r]))) for r in ref)
    for r in ref:
        assert float(np.max(np.abs(ref[r] - out[r]))) <= 1e-5 * scale


@needs_mesh
def test_mesh_stats_pass_schema_validation():
    from repro.telemetry.schema import validate_engine_stats

    wl = workload_for(TINY_TTI_CASCADE)
    params = wl.init(jax.random.PRNGKey(0))
    eng = ServeEngine(wl, params,
                      ServeConfig(max_batch=2, buckets=(8,),
                                  queue_capacity=2,
                                  mesh=make_debug_mesh(4, 2)))
    rng = np.random.default_rng(0)
    for rid in range(2):
        eng.submit(rid, rng.integers(0, wl.prompt_vocab, size=8))
    eng.run()
    validate_engine_stats(eng.stats, eng.route)


@needs_mesh
@pytest.mark.parametrize("shape", [(8, 1), (4, 2)])
def test_pallas_kernels_run_per_device_on_a_mesh(shape):
    """Mosaic kernels are not partitioned automatically, so under a mesh
    each Pallas call runs per device through ``shard_map``: batch split
    over ``data``, a TP-sharded weight gathered whole.  The interpret tier
    then matches its single-device result."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.conv2d import ops as conv_ops
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.groupnorm_silu import ops as gn_ops

    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (8, 8, 8, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 16, 16)) * 0.2
    q = jax.random.normal(jax.random.fold_in(key, 2), (8, 64, 2, 8))

    def f(x, w, q):
        y, st = conv_ops.conv2d(x, w, emit_stats=True, impl="interpret")
        g = gn_ops.groupnorm_silu(y, w[0, 0, 0], w[0, 0, 1], groups=4,
                                  impl="interpret")
        return g, st, fa_ops.attention(q, q, q, impl="interpret")

    want = f(x, w, q)
    mesh = make_debug_mesh(*shape)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, None, None, "model")))
    qs = jax.device_put(q, NamedSharding(mesh, P("data")))
    with mesh:
        assert str(jax.make_jaxpr(f)(xs, ws, qs)).count("shard_map") == 3
        got = f(xs, ws, qs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
