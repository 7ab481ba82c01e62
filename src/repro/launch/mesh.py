"""Production mesh builders.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The single-pod mesh is 16x16 = 256 chips
(one v5e pod slice); multi-pod stacks a leading ``pod`` axis (2 pods = 512
chips) used for data parallelism across the inter-pod (DCN/ICI-expanded)
links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n_axes):
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = 1
    for s in shape:
        ndev *= s
    devices = jax.devices()[:ndev]
    if len(devices) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for the production mesh, have "
            f"{len(devices)}; run under dryrun.py which forces 512 host "
            f"platform devices")
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(shape), axes, **_axis_kwargs(len(axes)))


def make_pod_mesh(axes=("pod", "data")):
    """Global mesh over every pod process's devices: one ``pod`` row per
    process, that process's local devices along ``data``.

    Device order is process-major (sorted by ``process_index``), which is
    the contract ``Batcher.dispatch_pod`` relies on: the global batch's
    leading dim sharded over ``("pod", "data")`` puts host *h*'s slab of
    rows on host *h*'s devices, so results scatter back without any
    cross-host gather.  Single-process this is a ``1 x n_local`` mesh and
    everything degrades to the ordinary data-parallel path.  Requires a
    bootstrapped pod (``repro.launch.multihost.bootstrap``) when
    ``jax.process_count() > 1``.
    """
    import numpy as np
    procs = jax.process_count()
    devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    if len(devices) % procs:
        raise RuntimeError(
            f"make_pod_mesh: {len(devices)} devices do not divide over "
            f"{procs} processes (heterogeneous hosts are unsupported)")
    local = len(devices) // procs
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(procs, local), axes,
        **_axis_kwargs(len(axes)))


def make_local_mesh(shape=None, axes=("data", "model")):
    """Smoke/test mesh over whatever devices exist (usually 1 CPU)."""
    import numpy as np
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return jax.sharding.Mesh(
        np.asarray(jax.devices()[:shape[0] * shape[1]]).reshape(shape), axes,
        **_axis_kwargs(len(axes)))
