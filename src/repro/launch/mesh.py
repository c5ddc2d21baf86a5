"""Production mesh + sharding resolution.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (16, 16) = ('data', 'model'); multi-pod:
(2, 16, 16) = ('pod', 'data', 'model') — 512 chips.

Param sharding roles (models/layers.py) resolve here:
  'fsdp' -> ('pod','data') [multi-pod] or ('data',)   # FSDP product axes
  'tp'   -> 'model'                                   # tensor parallel
  'exp'  -> 'model'                                   # expert parallel
Activations are batch-sharded over the FSDP axes.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.models.layers import ShardCtx


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model's with_sharding_constraint hints need them
    # (make_mesh defaults to Explicit axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def role_to_axes(mesh: Mesh):
    fsdp = batch_axes(mesh)
    return {"fsdp": fsdp if len(fsdp) > 1 else fsdp[0],
            "tp": "model", "exp": "model", "batch": fsdp}


def resolve_spec(role_spec: tuple, mesh: Mesh) -> P:
    """('fsdp','tp') -> PartitionSpec(('pod','data'), 'model') etc."""
    roles = role_to_axes(mesh)
    return P(*[roles.get(r) if r is not None else None for r in role_spec])


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def param_shardings(model, mesh: Mesh):
    """NamedSharding tree matching model.abstract_params().

    Dims that don't divide evenly by their mapped axis (smoke configs,
    small recurrent head counts) fall back to replication."""
    specs = model.param_specs()
    abstract = model.abstract_params()

    def resolve(rs, sds):
        roles = role_to_axes(mesh)
        rs = tuple(rs) + (None,) * (len(sds.shape) - len(rs))
        dims = []
        for dim_size, r in zip(sds.shape, rs):
            ax = roles.get(r) if r is not None else None
            if ax is not None and dim_size % _axes_size(mesh, ax) != 0:
                ax = None
            dims.append(ax)
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(resolve, specs, abstract,
                        is_leaf=lambda x: isinstance(x, tuple))


def serve_param_shardings(model, mesh: Mesh):
    """§Perf serving policy: undo FSDP (replicate over pod/data axes),
    keep TP — kills the per-decode-step parameter all-gather for models
    whose TP shards fit HBM."""
    base = param_shardings(model, mesh)
    drop = set(batch_axes(mesh))

    def strip(ns: NamedSharding):
        dims = []
        for d in ns.spec:
            if d is None or d in drop:
                dims.append(None)
            elif isinstance(d, tuple):
                kept = tuple(a for a in d if a not in drop)
                dims.append(kept if kept else None)
            else:
                dims.append(d)
        return NamedSharding(mesh, P(*dims))
    return jax.tree.map(strip, base)


def shard_ctx(mesh: Mesh) -> ShardCtx:
    return ShardCtx(mesh=mesh, batch_axes=batch_axes(mesh),
                    tp_axis="model")


def batch_sharding(mesh: Mesh, ndim: int, batch_dim: int = 0):
    dims = [None] * ndim
    dims[batch_dim] = batch_axes(mesh)
    return NamedSharding(mesh, P(*dims))
