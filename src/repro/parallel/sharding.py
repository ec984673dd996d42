"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Every parameter in the framework carries a tuple of *logical* axis names
(from ``Module.specs()``).  This module maps them onto the physical mesh:

    mesh axes: ("pod", "data", "model")  [multi-pod]  /  ("data", "model")

Parallelism encoded by the default rules:
  * FSDP / ZeRO-3 — the "embed" axis of every weight shards over ``data``
    (weights gather on use, gradients reduce-scatter), optimizer state
    inherits the same sharding.
  * TP — "mlp" / "heads" / "vocab" axes shard over ``model``.
  * EP — "experts" shards over ``model`` (MoE expert parallelism).
  * DP — the batch dim of activations shards over ``("pod", "data")``:
    cross-pod traffic is the gradient all-reduce only (DCN-friendly).
  * SP — KV caches shard their sequence axis over ``model`` at decode
    (flash-decoding style); prefill activations shard batch over data.

A rule only applies when the dimension divides the axis size (e.g. GQA
kv_heads=8 on a model axis of 16 stays replicated) — this keeps one rule set
valid across all ten architectures.
"""

from __future__ import annotations

import warnings
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.telemetry import Counter

# logical axis -> preferred mesh axis (None = replicate)
DEFAULT_RULES: dict[str, Any] = {
    "embed": "data",  # FSDP
    "embed2": None,
    "mlp": "model",  # TP
    "mlp2": None,
    "heads": "model",
    "kv_heads": "model",
    "kv_heads_small": None,  # GQA with kv < TP width: replicate (see attention.py)
    "vocab": "model",
    "experts": "model",  # EP
    "layers": None,
    "conv_in": None,
    "conv_out": None,
    "norm": None,
    None: None,
}

# Pure data parallelism + ZeRO-3 over the whole chip grid: no per-layer TP
# activation all-reduces — the right profile for models whose layers fit a
# chip (the §Perf hillclimb shows the crossover vs "2d").
FSDP_RULES: dict[str, Any] = {
    **DEFAULT_RULES,
    "embed": ("data", "model"),
    "mlp": None,
    "heads": None,
    "kv_heads": None,
    "vocab": None,
    "experts": "model",
}

# Serving profile: weights replicated across the data axis (TP-sharded on
# model only) — no ZeRO gathers on the per-token critical path.  The §Perf
# optimized sweep uses this for decode cells: FSDP-at-use is a training
# memory trade that is exactly wrong for single-token decode.
SERVE_RULES: dict[str, Any] = {
    **DEFAULT_RULES,
    "embed": None,
}

# Serving TP for conv-dominated stages: the paper's Fig.7 puts Conv at up to
# 44% of Diffusion-TTI time, and the reduced SR UNets are attention-free
# (attn_levels=()), so head/mlp TP alone leaves them fully replicated.
# Channel-parallel conv (shard "conv_out" over model) is the classic
# Megatron-style split for UNets: each shard computes a channel slice and
# the following layer consumes it replicated.
SERVE_TP_RULES: dict[str, Any] = {
    **SERVE_RULES,
    "conv_out": "model",
}

PROFILES = {
    "2d": {"rules": DEFAULT_RULES, "batch": ("pod", "data")},
    "fsdp": {"rules": FSDP_RULES, "batch": ("pod", "data", "model")},
    "serve": {"rules": SERVE_RULES, "batch": ("pod", "data")},
}

# Telemetry for the divisibility fallback below: silent replication is the
# classic TP foot-gun (a mis-sized mesh quietly serves fully replicated).
REPLICATION_FALLBACKS = Counter(
    "sharding_replication_fallbacks",
    "param dims that fell back to replication (dim % axis_size != 0)",
)
_warned_fallbacks: set = set()

_current_profile = "2d"


def set_profile(name: str) -> None:
    global _current_profile
    if name not in PROFILES:
        raise KeyError(f"unknown sharding profile {name!r}; have {list(PROFILES)}")
    _current_profile = name


def current_profile() -> str:
    return _current_profile


def current_rules() -> dict:
    return PROFILES[_current_profile]["rules"]


def batch_axes(mesh: Mesh) -> tuple:
    """Mesh axes over which the batch dim shards (DP), per active profile."""
    want = PROFILES[_current_profile]["batch"]
    return tuple(a for a in want if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def spec_for(
    logical_axes: tuple,
    shape: tuple,
    mesh: Mesh,
    rules: dict | None = None,
) -> P:
    """Logical axes tuple + concrete shape -> PartitionSpec, respecting
    divisibility (a dim that doesn't divide its axis stays replicated)."""
    rules = rules or current_rules()
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    out = []
    used: set = set()
    for name, dim in zip(logical_axes, shape):
        axis = rules.get(name, None)
        if axis is None:
            out.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        if any(a not in mesh.axis_names for a in axes) or any(a in used for a in axes):
            out.append(None)
            continue
        if dim % _axis_size(mesh, axis) != 0:
            # e.g. kv_heads=8 on model=16 — legal, but must not be silent.
            REPLICATION_FALLBACKS.inc()
            sig = (name, dim, axis, _axis_size(mesh, axis))
            if sig not in _warned_fallbacks:
                _warned_fallbacks.add(sig)
                warnings.warn(
                    f"sharding: logical axis {name!r} (dim={dim}) does not "
                    f"divide mesh axis {axis!r} (size={sig[3]}); replicating. "
                    "Check engine.stats['mesh']['params'] for TP coverage.",
                    stacklevel=2,
                )
            out.append(None)
            continue
        out.append(axis)
        used.update(axes)
    return P(*out)


def logical_to_sharding(specs_tree, shapes_tree, mesh: Mesh, rules=None):
    """Map a specs pytree (tuples of logical names) + matching shapes pytree
    (ShapeDtypeStruct or arrays) -> NamedSharding pytree."""

    def one(axes, shaped):
        return NamedSharding(mesh, spec_for(tuple(axes), tuple(shaped.shape), mesh, rules))

    return jax.tree.map(
        one, specs_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x
        ),
    )


def shard_params_tree(params, specs_tree, mesh: Mesh, rules=None):
    """Device-put a concrete params pytree according to the rules."""
    shardings = logical_to_sharding(specs_tree, params, mesh, rules)
    return jax.tree.map(jax.device_put, params, shardings)


def shard_report(params, specs_tree, mesh: Mesh, rules=None) -> dict:
    """Sharded-vs-replicated byte accounting ("TP coverage") for a params
    tree under the given rules — surfaced in ``engine.stats["mesh"]`` so a
    mesh that silently replicates everything is visible, not a mystery OOM.
    """
    shardings = logical_to_sharding(specs_tree, params, mesh, rules)
    sharded = 0
    replicated_b = 0

    def one(x, s):
        nonlocal sharded, replicated_b
        nbytes = int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
        if any(p is not None for p in s.spec):
            sharded += nbytes
        else:
            replicated_b += nbytes

    jax.tree.map(one, params, shardings)
    total = sharded + replicated_b
    return {
        "sharded_bytes": sharded,
        "replicated_bytes": replicated_b,
        "total_bytes": total,
        "tp_coverage": (sharded / total) if total else 0.0,
    }


def ambient_mesh() -> Mesh | None:
    """The mesh of the enclosing ``with mesh:`` context, else None."""
    from jax._src import mesh as mesh_lib

    env_mesh = mesh_lib.thread_resources.env.physical_mesh
    if env_mesh.empty:
        env_mesh = mesh_lib.get_concrete_mesh()
        if env_mesh is None or getattr(env_mesh, "empty", True):
            return None
    return env_mesh


def kernel_on_mesh(fn, args: tuple, batched: tuple):
    """``fn(*args)`` for a Pallas kernel call, run per device through
    ``shard_map`` when an ambient mesh holds several devices: Mosaic kernels
    cannot be partitioned automatically.

    Args flagged in ``batched`` split their leading (batch) dim over the
    mesh's batch axes when it divides; every other arg (weights, biases) is
    whole on each device, so on the ``model`` axis each shard runs the
    whole kernel.  Outputs are batch-major.  ``None`` args pass through."""
    mesh = ambient_mesh()
    if mesh is None or mesh.devices.size == 1:
        return fn(*args)
    first = next(a for a, b in zip(args, batched) if b and a is not None)
    lead = batch_sharding_for(mesh, first.shape[0], 1).spec[0]
    keep = [i for i, a in enumerate(args) if a is not None]

    def body(*xs):
        full = list(args)
        for i, x in zip(keep, xs):
            full[i] = x
        return fn(*full)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(P(lead) if batched[i] else P() for i in keep),
        out_specs=P(lead), check_vma=False,
    )(*(args[i] for i in keep))


def constrain(x, spec_names: tuple):
    """Activation sharding constraint using the ambient mesh context.

    ``spec_names`` entries: "batch" (expands to the pod x data axes), a mesh
    axis name, or None.  No-op outside a mesh context (unit tests) and for
    dims that don't divide their axis (long_500k batch=1 stays replicated).
    """
    env_mesh = ambient_mesh()
    if env_mesh is None:
        return x
    parts = []
    used: set = set()
    for dim, name in zip(x.shape, spec_names):
        if name is None:
            parts.append(None)
            continue
        axes = batch_axes(env_mesh) if name == "batch" else (
            name if isinstance(name, tuple) else (name,)
        )
        axes = tuple(a for a in axes if a in env_mesh.axis_names and a not in used)
        # largest divisible prefix
        while axes:
            n = 1
            for a in axes:
                n *= env_mesh.shape[a]
            if dim % n == 0:
                break
            axes = axes[:-1]
        if not axes:
            parts.append(None)
            continue
        used.update(axes)
        parts.append(axes if len(axes) > 1 else axes[0])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(env_mesh, P(*parts))
    )


def concat_unsharded(xs, axis: int = -1):
    """``jnp.concatenate`` with the concatenated axis pinned unsharded.

    XLA's CPU backend miscompiles ``concatenate`` along a *sharded*
    dimension: silently wrong values, eager and jitted alike, even when
    every operand carries the identical sharding (verified on a 4x2 host
    mesh).  Concats along unsharded axes are unaffected, as are adds and
    reshapes.  Every model-code concat on a dimension the TP rules may
    shard (conv channels, the expert-major MoE combine buffer) must route
    through here: dim 0 keeps its data-parallel batch axes, every other
    dim — in particular the concat axis — is pinned replicated.  The
    OUTPUT is pinned too: under jit the partitioner propagates a sharded
    layout backward onto the concat from downstream sharded-weight ops,
    and a concat whose result is sharded miscompiles even with replicated
    operands.  Downstream matmuls/convs re-shard via their weight
    shardings, so the only cost is one all-gather at the seam.  No-op
    outside a mesh context.
    """
    import jax.numpy as jnp

    xs = list(xs)
    nd = xs[0].ndim
    ax = axis % nd
    spec = tuple("batch" if (i == 0 and ax != 0) else None for i in range(nd))
    out = jnp.concatenate([constrain(x, spec) for x in xs], axis=axis)
    return constrain(out, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, *trailing) -> NamedSharding:
    """Batch-sharded activation: P((pod, data), *trailing)."""
    ba = batch_axes(mesh)
    lead = ba if len(ba) > 1 else (ba[0] if ba else None)
    return NamedSharding(mesh, P(lead, *trailing))


def batch_sharding_for(mesh: Mesh, global_batch: int, ndim: int,
                       trailing: tuple = ()) -> NamedSharding:
    """Shard dim-0 over (pod, data) if divisible, else over data, else
    replicate (long_500k has batch=1)."""
    ba = batch_axes(mesh)
    # try the largest divisible prefix product
    for k in range(len(ba), 0, -1):
        axes = ba[:k]
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if global_batch % n == 0:
            lead = axes if len(axes) > 1 else axes[0]
            spec = [lead] + [None] * (ndim - 1)
            for i, t in enumerate(trailing):
                spec[i + 1] = t
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P(*([None] * ndim)))
