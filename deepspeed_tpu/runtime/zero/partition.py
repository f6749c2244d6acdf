"""ZeRO partitioning as sharding specs over the mesh.

The reference implements ZeRO with flat buffers, per-param hooks, IPG buckets
and side streams (``runtime/zero/stage_1_and_2.py``, ``stage3.py``,
``partition_parameters.py``). The TPU-native formulation (SURVEY §7.1) is
*sharding-by-construction*: every stage is a placement policy for the three
pytrees involved in a training step —

===== =================== ====================== =======================
stage params (compute dt)  gradients              optimizer state (fp32
                                                  master + moments)
===== =================== ====================== =======================
0     replicated           psum → replicated      replicated
1     replicated           psum → replicated      sharded over zero axis
2     replicated           reduce-scattered       sharded
3     sharded              reduce-scattered       sharded
===== =================== ====================== =======================

The "zero axis" is ``("data", "fsdp")`` — ZeRO partitions across the whole
data-parallel world exactly like the reference's per-DP-rank partitions
(stage_1_and_2.py:167). XLA's SPMD partitioner then materializes the
collectives the reference hand-codes: all-gather of stage-3 params before
each consuming matmul (the analog of fetch_sub_module,
partitioned_param_coordinator.py:239), reduce-scatter of grads
(average_tensor, stage_1_and_2.py:937) and all-gather of updated weights
after the step (stage_1_and_2.py:1743) — all overlapped by the
latency-hiding scheduler instead of a manual side stream.

What these placements do NOT decide is whether a stage-3 matmul gathers
its weight or its activation: with ``x`` and ``W`` both split over the
zero axis the partitioner may as well leave ``W`` split and exchange
activations (the tensor-parallel reading), and on GPT-2 1.3B over
``fsdp=4`` it does: 30.5 GB over the wire a chip a step against the 5.8
of two parameter gathers and one gradient scatter. The model's
residual-stream constraint (``models/gpt2.py``:
``maybe_constrain(x, P(DATA_AXES, "seq", None))``, resolved against the
engine's mesh by ``utils/sharding.py``) pins activations to the batch
axes, and that is what makes "all-gather the weight" the only reading
left. ZeRO-3 as the table describes it rests on that constraint; a model
without it gets correct losses and the wrong traffic.

Per-leaf placement: shard the largest dimension that is divisible by the
zero-axis size and not already claimed by tensor parallelism. Leaves smaller
than ``param_persistence_threshold`` stay replicated — same intent as the
reference's persistent small params (parameter_offload.py:316).
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.logging import logger

ZERO_AXES = ("data", "fsdp")  # combined ZeRO partitioning axis


def _zero_axis_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in ZERO_AXES if a in mesh.shape]))


def _spec_entry_axes(entry):
    """Mesh axes already used by one PartitionSpec entry."""
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def shard_leaf_spec(shape, base_spec: Optional[P], mesh: Mesh,
                    min_size: int = 0) -> P:
    """Extend ``base_spec`` (TP placement) with ZeRO sharding of one dim.

    Picks the largest divisible, unclaimed dimension; returns ``base_spec``
    unchanged if nothing fits (small/odd-shaped leaves stay replicated —
    they are cheap and XLA handles them fine).
    """
    def clean(entries):
        return P(*entries) if any(e is not None for e in entries) else P()

    zsize = _zero_axis_size(mesh)
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    if zsize <= 1 or int(np.prod(shape) if shape else 1) < max(min_size, zsize):
        return clean(base)
    used = set()
    for e in base:
        used.update(_spec_entry_axes(e))
    zero_axes = tuple(a for a in ZERO_AXES if a in mesh.shape and
                      mesh.shape[a] > 1 and a not in used)
    if not zero_axes:
        return clean(base)
    zdiv = int(np.prod([mesh.shape[a] for a in zero_axes]))
    # largest dim that divides evenly and isn't already sharded
    candidates = [(dim_size, i) for i, dim_size in enumerate(shape)
                  if base[i] is None and dim_size % zdiv == 0]
    if not candidates:
        return clean(base)
    _, idx = max(candidates)
    new = list(base)
    new[idx] = zero_axes[0] if len(zero_axes) == 1 else zero_axes
    return P(*new)


def _normalize_base(tp_spec, ndim):
    base = tuple(tp_spec) if tp_spec is not None else ()
    return base + (None,) * (ndim - len(base))


class ZeroShardingPolicy:
    """Computes NamedShardings for the param/grad/opt-state pytrees.

    ``tp_specs``: optional pytree (matching params) of PartitionSpecs carrying
    tensor/seq-parallel placement from the model's sharding rules; ZeRO
    sharding composes on top of unclaimed dims.
    """

    def __init__(self, stage: int, mesh: Mesh, tp_specs=None,
                 param_persistence_threshold: int = 0):
        if stage not in (0, 1, 2, 3):
            raise ValueError(f"invalid ZeRO stage {stage}")
        self.stage = stage
        self.mesh = mesh
        self.tp_specs = tp_specs
        self.threshold = param_persistence_threshold
        self._warned_uneven: set = set()

    def _tp_spec_for(self, path):
        if self.tp_specs is None:
            return None
        leaf = self.tp_specs
        for k in path:
            key = getattr(k, "key", getattr(k, "idx", getattr(k, "name", None)))
            if isinstance(leaf, dict):
                leaf = leaf.get(key)
            else:
                return None
            if leaf is None:
                return None
        return leaf if isinstance(leaf, P) else None

    def _map(self, params_like, fully_shard: bool):
        def per_leaf(path, leaf):
            shape = getattr(leaf, "shape", ())
            tp = self._tp_spec_for(path)
            if fully_shard:
                spec = shard_leaf_spec(shape, tp, self.mesh, self.threshold)
            else:
                base = _normalize_base(tp, len(shape))
                spec = P(*base) if any(e is not None for e in base) else P()
            self._check_divisible(path, shape, spec, model_spec=tp)
            return NamedSharding(self.mesh, spec)
        return jax.tree_util.tree_map_with_path(per_leaf, params_like)

    # EP placement rides the data-parallel axes (moe/sharded_moe.py puts
    # stacked expert weights on data×fsdp); these are the axes whose
    # divisibility the dispatch all-to-all genuinely requires
    _EP_AXES = frozenset(("data", "fsdp"))

    def _check_divisible(self, path, shape, spec, model_spec=None) -> None:
        """Model-provided TP/EP specs are applied verbatim. A dim the
        MODEL placed on the EP axes (data/fsdp — an expert dim) that does
        not divide them is a hard error: the MoE dispatch all-to-all
        requires equal expert shards, and the failure would otherwise
        surface much later as an opaque pjit out_sharding error. Any
        other uneven dim (e.g. an unpadded vocab on the tensor axis, or
        ZeRO's own stage-3 composition) is legal under GSPMD — XLA pads
        the ragged shard — so it only gets a one-line warning about the
        padding waste, not a refusal (ADVICE r3: uneven TP configs worked
        before the check landed and must keep working). Keyed on the
        dim's axes, not the leaf's name — an expert leaf's uneven plain-
        TP dim warns; an expert dim on a leaf named anything raises."""
        model_base = _normalize_base(model_spec, len(shape))
        for i, entry in enumerate(tuple(spec)):
            axes = _spec_entry_axes(entry)
            if not axes:
                continue
            div = int(np.prod([self.mesh.shape[a] for a in axes]))
            if div > 1 and shape[i] % div:
                name = jax.tree_util.keystr(path)
                model_axes = set(_spec_entry_axes(model_base[i]))
                if model_axes & self._EP_AXES:
                    raise ValueError(
                        f"param {name!r} dim {i} (size {shape[i]}) is not "
                        f"divisible by mesh axes {tuple(axes)} (product "
                        f"{div}) required by its sharding spec {spec}. "
                        f"The expert dispatch all-to-all needs equal "
                        f"shards — make num_experts a multiple of the "
                        f"data*fsdp extent (or shrink the mesh).")
                # _map runs once per placement (param/grad/opt-state) —
                # dedup so one ragged leaf warns once per engine init
                if (name, i) not in self._warned_uneven:
                    self._warned_uneven.add((name, i))
                    logger.warning(
                        "param %r dim %d (size %d) is not divisible by "
                        "mesh axes %s (product %d); GSPMD pads the ragged "
                        "shard — fine, but padding the dim to a multiple "
                        "avoids the wasted memory/compute", name, i,
                        shape[i], tuple(axes), div)

    # -- the three placements ------------------------------------------------

    def param_sharding(self, params_like):
        """Compute-dtype params: sharded only at stage 3."""
        return self._map(params_like, fully_shard=self.stage >= 3)

    def grad_sharding(self, params_like):
        """Gradient accumulator: reduce-scattered at stage >= 2."""
        return self._map(params_like, fully_shard=self.stage >= 2)

    def master_sharding(self, params_like):
        """fp32 master weights + optimizer moments: sharded at stage >= 1."""
        return self._map(params_like, fully_shard=self.stage >= 1)

    def spec_of(self, sharding_tree):
        return jax.tree.map(lambda s: s.spec, sharding_tree,
                            is_leaf=lambda x: isinstance(x, NamedSharding))
