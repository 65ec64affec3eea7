"""Production mesh definitions.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

Mesh layout (DESIGN.md §6):
  single-pod:  (16, 16)        axes ("data", "model")   — 256 chips (v5e pod)
  multi-pod:   (2, 16, 16)     axes ("pod", "data", "model") — 512 chips

DP runs over ("pod", "data") (hierarchical all-reduce: reduce-scatter inside
a pod over "data", cross-pod all-reduce over "pod" — XLA's collective
scheduler emits exactly this decomposition for the nested axes), TP/EP over
"model".
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto (sharding-propagation)
    mode, the mode the shard_map and jit code here is written for."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever this host has — used by smoke tests and CPU examples."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_spec(mesh):
    from jax.sharding import PartitionSpec as P
    return P(data_axes(mesh))


def filter_spec(spec, mesh):
    """Drop axis names a mesh doesn't have (e.g. 'pod' on single-pod) from a
    PartitionSpec, so parameter specs can always name the full DP hierarchy."""
    from jax.sharding import PartitionSpec as P
    names = set(mesh.axis_names)

    def filt(a):
        if a is None:
            return None
        if isinstance(a, (tuple, list)):
            kept = tuple(x for x in a if x in names)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return a if a in names else None

    return P(*[filt(a) for a in spec])


def shardings_for(mesh, spec_tree):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(lambda sp: NamedSharding(mesh, filter_spec(sp, mesh)),
                        spec_tree, is_leaf=lambda x: isinstance(x, P))
