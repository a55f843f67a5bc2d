"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — required because the dry-run forces 512 host
devices via XLA_FLAGS *before* any jax initialisation, while tests/benches
must see the real single device.

  single-pod: (16, 16)      axes ("data", "model")   — 256 chips (v5e pod)
  multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") — 512 chips;
              the "pod" axis is pure data parallelism whose gradient
              all-reduce crosses the DCN (slow links) — kept outermost so
              XLA's hierarchical collectives do ICI reduce-scatter first.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes, devices=None):
    # Auto axes: sharding is steered by with_sharding_constraint and
    # NamedSharding placements, never by explicit-axis typing
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """A 1×1 mesh on the real local device — smoke tests of the pjit path."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_serving_mesh(data: int = None, model: int = None, *,
                      devices=None):
    """A (data, model) mesh over the available devices — the serving mesh.

    On CI this is the forced-host path: run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and jax exposes
    N CPU "devices", so the full NamedSharding/SPMD machinery (param
    layouts, activation constraints, collective insertion) compiles and
    executes exactly as it would on a real slice. With both factors None
    the whole device set goes to "data" (pure lane parallelism — the
    bitwise-safe default for continuous batching: every collective is a
    gather/slice, never a split reduction). ``devices`` restricts to a
    subset (the benchmark's mesh-size sweep takes prefixes of
    ``jax.devices()``).
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if data is None and model is None:
        data, model = n, 1
    elif data is None:
        assert n % model == 0, (n, model)
        data = n // model
    elif model is None:
        assert n % data == 0, (n, data)
        model = n // data
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"),
                      devices=devs[: data * model])


def device_count() -> int:
    return len(jax.devices())


def data_axes(mesh) -> tuple:
    """All axes that carry pure data parallelism."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)
