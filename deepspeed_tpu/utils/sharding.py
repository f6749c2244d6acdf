"""Sharding helpers shared by model code.

**What a model's constraint decides.** The engine gives ``jax.jit``
the placements of its state and its batch (ZeRO-3: parameters, master
copies and moments split over ``data`` x ``fsdp``, the batch split over
the same axes) and leaves everything between them to XLA's SPMD
partitioner. For ``y = x @ W`` with BOTH operands split over ``fsdp``
the partitioner has two readings and nothing in the placements chooses
between them: gather the WEIGHT and keep the activations on the batch
axes (ZeRO-3: each chip computes its own rows, a layer's weights cross
the wire twice and its gradients once), or leave the weight split and
exchange the ACTIVATIONS (tensor parallelism: gather ``x`` or
reduce-scatter partial products, and reshard between the two layouts at
every residual add). Left to itself it picks the second on GPT-2 1.3B
over ``fsdp=4``: 30.5 GB over the wire a chip a step where the first
needs 5.8 (PERF.md section 6, PR 44). What decides is a constraint on
the ACTIVATIONS: the models pin the residual stream to the batch axes
(``maybe_constrain(x, P(DATA_AXES, "seq", None))`` after the
embedding), the partitioner carries that layout through the blocks, and
every matmul then sees a batch-split ``x`` against a split ``W`` and
gathers the weight. ZeRO-3's traffic rests on that one line; a model
written for the engine needs it too (docs/parallelism.md).

**Which mesh a bare spec means** (``engine_mesh``). Model code writes
``PartitionSpec``s, not shardings. The engine traces its step with
explicit ``NamedSharding``s and opens no mesh context, so the abstract
mesh is empty there and the mesh is the one the engine registered
(``comm.mesh.set_global_mesh``); the flash kernel's mapping
(``ops/attention.py``) and ``maybe_constrain`` both ask for it here.
Where a caller did open a context (``jax.set_mesh``, or the body of a
``shard_map``) that context rules: inside ``shard_map`` (the engine's
explicit-exchange DP steps, ring / Ulysses attention, the pipeline's
manual regions) axes are Manual, XLA rejects constraints over them, and
the caller owns the layout.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import get_global_mesh, has_global_mesh


def engine_mesh():
    """The mesh the engine registered, for code traced with no mesh
    context of its own; None where no engine has set one, or inside a
    manual region (the caller of a ``shard_map`` body owns the mapping)."""
    ctx = jax.sharding.get_abstract_mesh()
    if not has_global_mesh() or (not ctx.empty and ctx.manual_axes):
        return None
    return get_global_mesh()


def _entry_axes(entry) -> tuple:
    if entry is None or entry is P.UNCONSTRAINED:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def maybe_constrain(x, spec: P):
    """``with_sharding_constraint`` where it means something. Under a
    mesh context: every axis the spec names exists and is Auto. Under
    none: over the engine's mesh, when every named axis exists there, at
    least one has more than one device and every dimension divides by
    the devices it is split over. A spec whose axes all have extent 1
    asks for nothing the program does not already do, so one chip (and a
    mesh that is all ``tensor``) compiles what it compiled before; a
    batch that does not divide (a trained model applied to two rows
    while the engine's mesh of eight is still registered) stays where it
    is, as the flash kernel's mapping leaves it. Otherwise ``x`` as it
    is: bare-jit unit tests run with no mesh at all."""
    named = [ax for entry in spec for ax in _entry_axes(entry)]
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        types = dict(zip(ctx.axis_names, ctx.axis_types))
        if all(types.get(ax) == jax.sharding.AxisType.Auto for ax in named):
            return jax.lax.with_sharding_constraint(x, spec)
        return x
    mesh = engine_mesh()
    if mesh is None or any(ax not in mesh.shape for ax in named):
        return x
    split = [math.prod(mesh.shape[ax] for ax in _entry_axes(entry))
             for entry in spec]
    if max(split, default=1) == 1 or any(
            size % n for size, n in zip(x.shape, split)):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


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
