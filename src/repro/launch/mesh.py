"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — critical because smoke tests run with one
CPU device while the dry-run forces 512 virtual devices via XLA_FLAGS.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small-scale runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def parse_mesh(axes: str, shape: str | None = None):
    """CLI mesh spec -> Mesh (launch/serve.py ``--mesh``/``--mesh-shape``).

    ``axes`` is comma-separated axis names ("data,model"); ``shape`` is
    comma-separated sizes ("2,4"). When ``shape`` is omitted, all available
    devices go on the LAST axis (so ``--mesh data,model`` on 8 devices is a
    1x8 pure-TP serving mesh).
    """
    axis_names = tuple(a.strip() for a in axes.split(",") if a.strip())
    if not axis_names:
        raise ValueError(f"empty mesh axes spec {axes!r}")
    if shape:
        sizes = tuple(int(s) for s in shape.split(","))
        if len(sizes) != len(axis_names):
            raise ValueError(f"--mesh-shape {shape!r} has {len(sizes)} "
                             f"entries for {len(axis_names)} axes {axis_names}")
    else:
        sizes = (1,) * (len(axis_names) - 1) + (len(jax.devices()),)
    return make_mesh(sizes, axis_names)


def data_axis_names(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def split_data_replicas(mesh) -> list:
    """One serving submesh per index along the data axes — the DP x TP
    replica split (docs/DESIGN.md §14, serving/replica.py).

    A ``(data=R, model=T)`` mesh becomes R submeshes of shape
    ``(data=1, model=T)``: each keeps every axis NAME (so the TP-only
    serving specs resolve unchanged — a size-1 data axis shards nothing)
    but owns a disjoint 1/R slice of the devices. Weights placed per
    submesh are therefore replicated across replicas and TP-sharded
    within one. Meshes without a data axis (or with data=1) return
    ``[mesh]`` — plain single-replica serving.
    """
    import itertools

    import numpy as np

    names = mesh.axis_names
    axes = [names.index(a) for a in data_axis_names(mesh) if a in names]
    sizes = [mesh.devices.shape[a] for a in axes]
    if not axes or int(np.prod(sizes)) == 1:
        return [mesh]
    subs = []
    for idx in itertools.product(*(range(s) for s in sizes)):
        devs = mesh.devices
        for a, i in zip(axes, idx):
            devs = np.take(devs, [i], axis=a)
        subs.append(jax.sharding.Mesh(devs, names))
    return subs
