"""Sharding helpers shared by model code.

One definition for the "constrain if meaningful" rule (previously
duplicated in models/gpt2.py and moe/sharded_moe.py): apply
``with_sharding_constraint`` only when a mesh is in scope, every axis the
spec names exists, and those axes are Auto — inside ``shard_map`` (the
engine's explicit-exchange DP steps) axes are Manual and XLA rejects
constraints, and bare-jit unit tests run without a mesh at all.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def maybe_constrain(x, spec: P):
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    types = dict(zip(mesh.axis_names, mesh.axis_types))
    names = set(mesh.axis_names)
    for entry in spec:
        if entry is P.UNCONSTRAINED:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            if ax is not None and (
                    ax not in names or
                    types[ax] != jax.sharding.AxisType.Auto):
                return x
    return jax.lax.with_sharding_constraint(x, spec)


def map_kernel(kernel, mesh, in_specs, out_specs):
    """``kernel`` as it must be called under ``mesh``. GSPMD cannot
    partition a Mosaic (Pallas TPU) call — the lowering refuses with
    "wrap the call in a shard_map" — so on a mesh of several devices the
    kernel runs per shard, over the axes the specs name (attention is
    embarrassingly parallel in batch and heads); axes a spec does not
    name stay replicated. On one device, or with no mesh, the kernel is
    returned as is."""
    if mesh is None or mesh.size == 1:
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
