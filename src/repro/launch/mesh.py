"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and dryrun.py
must set XLA_FLAGS before that happens).

LM meshes:    (16, 16) -> ('data', 'model');  multi-pod (2, 16, 16) ->
              ('pod', 'data', 'model'). Batch shards over ('pod','data'),
              FSDP over 'data', tensor/expert parallelism over 'model'
              (per-arch fallbacks in models/lm.py choose_layout).
Self-join:    the paper's workload wants a 1-D spatial slab axis x an
              offset-parallel axis, so its mesh flattens pod x data into
              'slab': (16, 16) single-pod, (32, 16) multi-pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_compat(shape, axes):
    """Mesh of Auto axes over the first prod(shape) devices (tests and
    subprocess drivers build their meshes through this)."""
    return _mk(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_selfjoin_mesh(*, multi_pod: bool = False):
    shape = (32, 16) if multi_pod else (16, 16)
    return _mk(shape, ("slab", "model"))


def make_slab_mesh(n_slabs: int):
    """1-D slab mesh over the first ``n_slabs`` local devices -- the mesh
    shape of the distributed self-join (core/distributed.py) and the
    distributed bench/CI smokes. Unlike ``jax.make_mesh`` this accepts a
    strict subset of the devices, so a 2-slab smoke runs on any host with
    ``--xla_force_host_platform_device_count=2`` or more."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_slabs > len(devs):
        raise ValueError(
            f"make_slab_mesh({n_slabs}) needs {n_slabs} devices, have "
            f"{len(devs)} (set --xla_force_host_platform_device_count)")
    return Mesh(np.asarray(devs[:n_slabs]), ("slab",))


def make_smoke_mesh(n_devices: int = 1):
    """Tiny mesh over whatever devices exist (tests / CPU examples)."""
    n = min(n_devices, len(jax.devices()))
    model = 2 if n % 2 == 0 else 1
    return _mk((n // model, model), ("data", "model"))
