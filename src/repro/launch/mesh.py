"""Production mesh definitions and host-mesh (fake-device) setup.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and everything else must see the real single device.  For
the same reason ``jax`` is imported lazily inside each function:
:func:`ensure_host_device_count` must be importable (and callable) before
jax ever loads.
"""

from __future__ import annotations

import os
import re

_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def ensure_host_device_count(n: int = 512, *, respect_env: bool = True) -> int:
    """Set ``--xla_force_host_platform_device_count=n`` in ``XLA_FLAGS``.

    Must run before any jax import (jax locks the device count at backend
    init).  With ``respect_env`` (the default) an existing count in
    ``XLA_FLAGS`` wins — so ``XLA_FLAGS=--xla_force_host_platform_device_count=8
    python -m repro.launch.dryrun ...`` overrides a caller's hardcoded 512.
    Returns the count in effect.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = _COUNT_RE.search(flags)
    if m is not None:
        if respect_env:
            return int(m.group(1))
        flags = _COUNT_RE.sub(f"--xla_force_host_platform_device_count={n}", flags)
        os.environ["XLA_FLAGS"] = flags
        return n
    extra = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = f"{flags} {extra}".strip()
    return n


def parse_mesh(spec: str) -> tuple[int, int]:
    """Parse a ``--mesh DxM`` flag ("4x2" -> (4, 2): data=4, model=2)."""
    m = re.fullmatch(r"(\d+)\s*[xX]\s*(\d+)", spec.strip())
    if m is None:
        raise ValueError(f"bad mesh spec {spec!r}; expected DxM, e.g. 4x2")
    d, t = int(m.group(1)), int(m.group(2))
    if d < 1 or t < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return d, t


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
    pure data parallelism (cross-pod DCN carries only gradient all-reduce /
    no per-layer collectives)."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """(data, model) mesh over the first ``n_data * n_model`` local devices.

    Axes are ``Auto``: the serving path places params and batches with
    ``NamedSharding`` and lets the partitioner propagate the rest, which
    ``jax.make_mesh``'s default ``Explicit`` axes reject (e.g. a gather
    from a TP-sharded embedding table)."""
    import jax

    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
