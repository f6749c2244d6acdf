"""KV cache — the inference workspace.

Analog of the reference's singleton inference ``Context`` that owns one
growing KV-cache workspace sized from free GPU memory
(``csrc/transformer/inference/includes/inference_context.h:48,124-161``).
On TPU the cache must be a statically-shaped, donated pytree threaded
through the jitted decode step: ``[L, B, S_max, H_kv, D]`` ring of keys and
values plus per-sequence live ``lengths [B]``. Allocation is explicit
(``max_out_tokens`` config) instead of free-memory introspection, and
"workspace reuse across layers" becomes XLA buffer donation.
"""
from __future__ import annotations

import functools
import hashlib
from collections import OrderedDict
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from deepspeed_tpu.ops.quant_core import dequantize_int8, quantize_int8
from deepspeed_tpu.profiling.trace import scoped


@struct.dataclass
class KVCache:
    k: jnp.ndarray        # [L, B, S, H, D]
    v: jnp.ndarray        # [L, B, S, H, D]
    lengths: jnp.ndarray  # [B] int32 — live tokens per sequence

    @property
    def max_seq(self) -> int:
        return self.k.shape[2]

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]


def auto_max_tokens(num_layers: int, batch: int, num_kv_heads: int,
                    head_dim: int, dtype=jnp.bfloat16,
                    reserve_fraction: float = 0.1,
                    shard_factor: int = 1):
    """HBM-aware KV budget — the reference's free-memory workspace sizing
    (``inference_context.h:124-161``: workspace = free GPU memory at first
    forward × memory_gb knob) translated to the static-shape world: how
    many cache tokens per sequence fit the accelerator's CURRENTLY free
    memory, minus a safety reserve for activations/compile workspace.
    Returns ``None`` when the backend reports no memory stats (CPU tests,
    interpret mode) — callers fall back to the explicit default. Raises
    when stats exist but free memory cannot hold even a 128-token cache:
    silently clamping up would defer the failure to an opaque OOM at
    cache allocation.

    ``shard_factor``: how many ways the cache's sharded dims (kv-heads
    over ``tensor``, S over ``seq``) divide across devices — each device
    holds ``1/shard_factor`` of the per-token bytes, so the budget grows
    by that factor under model parallelism."""
    from deepspeed_tpu.accelerator import get_accelerator
    stats = get_accelerator().memory_stats()
    limit = int(stats.get("bytes_limit", 0))
    if limit <= 0:
        return None
    free = max(0, limit - int(stats.get("bytes_in_use", 0)))
    per_token = (num_layers * 2 * num_kv_heads * head_dim
                 * jnp.dtype(dtype).itemsize * batch
                 ) // max(int(shard_factor), 1)
    tokens = (int(free * (1.0 - reserve_fraction)) // max(per_token, 1)
              // 128) * 128
    if tokens < 128:
        # Clamping up to 128 here would pass the budget check and then
        # die at cache allocation with an opaque OOM; the 'auto' path
        # owes the caller the loud, knob-naming error instead.
        raise RuntimeError(
            "max_out_tokens='auto': free accelerator memory "
            f"({free / 2**20:.0f} MiB of {limit / 2**20:.0f} MiB limit) "
            f"cannot hold even a 128-token KV cache at {per_token} "
            "bytes/token — reduce batch/model size, free memory, or set "
            "max_out_tokens explicitly")
    return tokens


def init_cache(num_layers: int, batch: int, max_seq: int, num_kv_heads: int,
               head_dim: int, dtype=jnp.bfloat16) -> KVCache:
    shape = (num_layers, batch, max_seq, num_kv_heads, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   lengths=jnp.zeros((batch,), jnp.int32))


@scoped("kv_write")
def write_prompt(cache: KVCache, layer: int, k: jnp.ndarray, v: jnp.ndarray,
                 lengths: jnp.ndarray) -> KVCache:
    """Prefill: write ``[B, T, H, D]`` keys/values at positions 0..T-1.

    Right-padded positions hold garbage; they are either masked by decode
    (col >= lengths) or overwritten by subsequent appends at position
    ``lengths[b]``.
    """
    T = k.shape[1]
    newk = jax.lax.dynamic_update_slice(
        cache.k, k[None].astype(cache.k.dtype), (layer, 0, 0, 0, 0))
    newv = jax.lax.dynamic_update_slice(
        cache.v, v[None].astype(cache.v.dtype), (layer, 0, 0, 0, 0))
    return cache.replace(k=newk, v=newv, lengths=lengths.astype(jnp.int32))


@scoped("kv_write")
def append_token(cache: KVCache, layer: int, k: jnp.ndarray,
                 v: jnp.ndarray) -> KVCache:
    """Decode: append one token's ``[B, H, D]`` k/v at ``lengths[b]`` per row.

    Lengths are NOT advanced here (all layers append at the same position);
    call :func:`advance` once per step after the last layer.
    """
    def upd(cache_layer, x, i):
        # cache_layer [S, H, D], x [H, D]
        return jax.lax.dynamic_update_slice(cache_layer, x[None], (i, 0, 0))

    newk_l = jax.vmap(upd)(cache.k[layer], k.astype(cache.k.dtype),
                           cache.lengths)
    newv_l = jax.vmap(upd)(cache.v[layer], v.astype(cache.v.dtype),
                           cache.lengths)
    newk = jax.lax.dynamic_update_index_in_dim(cache.k, newk_l, layer, 0)
    newv = jax.lax.dynamic_update_index_in_dim(cache.v, newv_l, layer, 0)
    return cache.replace(k=newk, v=newv)


@scoped("kv_write")
def write_chunk(cache: KVCache, layer: int, k: jnp.ndarray,
                v: jnp.ndarray) -> KVCache:
    """Speculative verify: write a K-token chunk's ``[B, K, H, D]`` k/v
    at positions ``lengths[b] .. lengths[b]+K-1`` per row.

    Lengths are NOT advanced — the caller commits only the accepted
    prefix (rejected draft positions stay as garbage beyond ``lengths``,
    which attention masks and later writes overwrite, exactly like
    right-padding after :func:`write_prompt`)."""
    def upd(cache_layer, x, i):
        # cache_layer [S, H, D], x [K, H, D]
        return jax.lax.dynamic_update_slice(cache_layer, x, (i, 0, 0))

    newk_l = jax.vmap(upd)(cache.k[layer], k.astype(cache.k.dtype),
                           cache.lengths)
    newv_l = jax.vmap(upd)(cache.v[layer], v.astype(cache.v.dtype),
                           cache.lengths)
    newk = jax.lax.dynamic_update_index_in_dim(cache.k, newk_l, layer, 0)
    newv = jax.lax.dynamic_update_index_in_dim(cache.v, newv_l, layer, 0)
    return cache.replace(k=newk, v=newv)


def advance(cache: KVCache, n: int = 1) -> KVCache:
    return cache.replace(lengths=cache.lengths + n)


# ---------------------------------------------------------------- paged
# vLLM-style PagedAttention, translated to the static-shape TPU world: one
# global block pool ``[L, num_blocks, block_size, H*D]`` shared by every
# live sequence, plus a per-SLOT int32 block table mapping logical cache
# positions to pool blocks. All shapes are static, so the jitted decode
# step is traced ONCE per (num_slots, block_size) configuration and
# replayed for every request mix; allocation/recycling is host-side
# free-list bookkeeping (BlockAllocator) that never touches the trace.
#
# Block 0 is a reserved NULL block: idle slots keep an all-zero block
# table and length 0, so their (masked, discarded) appends land in block
# 0 instead of corrupting a live sequence's memory. The allocator never
# hands block 0 out.


@struct.dataclass
class PagedKVCache:
    """Paged decode workspace over ``num_slots`` resident sequences.

    k/v: ``[L, num_blocks, block_size, KH*D]`` (v: ``KH*Dv``, a value
    head's own width, where it is not a key head's) global pool, ONE
    stacked array each, stored in the byte order the paged kernels read
    (ops/pallas/decode_attention.py): a position's row is its
    ``num_kv_heads`` heads side by side, ``KH*D`` lanes wide, so a block
    is one contiguous ``[block_size, KH*D]`` slab and the kernels take
    the whole array as their operand — a layer is a block offset in
    their index map, and no program copies a layer out of the pool or
    reorders it on the way to a kernel. (On a TPU ``[.., BS, KH, D]``
    and ``[.., BS, KH*D]`` are different byte orders: stored the first
    way, every kernel call paid a layer-sized conversion of K and of V.)
    ``KH*D`` should be a multiple of the 128 lanes; a narrower or ragged
    row is stored the other way round by the compiler and converted
    around every call. Writers reshape the NEW rows, never the pool.
    num_kv_heads: static; how the lanes of a POOL row split into heads
    (``head_dim``, a key head's width, and ``v_head_dim``, a value
    head's, follow from the two arrays: equal unless the cache was built
    with its own ``v_head_dim``). Under a ``tensor`` mesh the lane dim is
    the sharded one: heads are its major part, so a shard keeps whole
    heads.
    ring_kv_heads: static; how the lanes of a RING row split (None: as
    the pool's). The two kinds of place may keep different head counts
    (a model whose window layers carry 8 key/value heads beside 4 on its
    full layers): four row widths in one cache object, one allocator,
    one set of block tables.
    block_tables: ``[num_slots, max_blocks]`` int32 — pool block ids per
    slot, in logical order (entry j covers positions
    ``j*block_size .. (j+1)*block_size-1``); unallocated entries are 0
    (the null block).
    lengths: ``[num_slots]`` int32 live context length per slot.

    int8 storage (``kv_cache_dtype: "int8"``): k/v hold int8 payloads
    and ``k_scale``/``v_scale`` carry the per-block-per-head scale
    tiles beside the pool — ``[L, NB, KH, BS]`` f32, one symmetric
    amax/127 scale per written (position, head) row (ops/quant_core.py;
    the SwitchBack per-axis idiom), laid out so a Pallas kernel's scale
    block ``(1, KH, BS)`` puts the block_size positions on the lane dim.
    Writers quantize on write; readers apply the scales in-kernel (VMEM)
    or dequantize at the gather. Scales are DATA in the same donated pytree — tier
    membership and quantization never change a traced signature.
    ``None`` scales = full-precision pool (the default).

    Window layers (``layer_map``; None = every layer keeps its whole
    context, the default): a layer whose attention sees only the last
    ``window`` positions keeps a bounded RING a slot instead of blocks of
    the pool: ``ring_k`` / ``ring_v`` ``[Lw, S*RB, BS, KHw*D]`` /
    ``[.., KHw*Dv]``, the pool's block and head widths (``KHw``:
    ``ring_heads``, the pool's count unless ``ring_kv_heads`` says
    otherwise), slot ``s``'s ring the ``RB`` consecutive
    blocks from ``s*RB``, position ``p`` at row ``p mod (RB*BS)`` of it.
    A ring never grows, takes no block of the pool and no entry of the
    block tables (admission counts the other layers' blocks only), and
    is overwritten by the slot's next prefill. ``k`` / ``v`` then hold
    the OTHER layers only: model layer ``l`` is ``layer_map[l] = (kind,
    i)``, ``i`` the layer's index in the pool (``"full"``) or in the
    rings (``"window"``).

    State layers (``layer_map`` kind ``"state"``): a layer that keeps a
    recurrent STATE and no row a token (a state-space mixer) is a third
    place under the same map: ``state[i]`` ``[S, *state_shape]`` (float32
    unless asked otherwise), one slot-major buffer a state layer, and
    ``conv[i]`` ``[taps, S, C]``, the last ``taps`` inputs of the layer's
    short convolution (tap-major: the slots and the channels tile the
    sublanes and lanes, a 3-wide minor dim would be padded to 128). As a
    ring, a state is a fixed cost a slot whatever the context: it takes
    no block of the pool and no entry of the block tables, a prefill
    writes one slot of a donated buffer in place, and a retired slot's
    state is overwritten by the next prefill and never read.

    Layers that keep NOTHING (``layer_map`` kind ``"none"``): a layer
    with no mixer over the sequence (an expert layer or an MLP that is a
    layer of its own) has a place in the map and none in the cache: no
    slab of the pool, no ring, no state. The pool's ``L`` counts the
    ``"full"`` layers only.
    aux: an int32 array that belongs to the MODEL, as
    :class:`LatentPagedCache`'s (None: the model counts nothing)."""
    k: jnp.ndarray             # [L, NB, BS, KH*D] (fp or int8)
    v: jnp.ndarray             # [L, NB, BS, KH*Dv]
    block_tables: jnp.ndarray  # [S, MB] int32
    lengths: jnp.ndarray       # [S] int32
    num_kv_heads: int = struct.field(pytree_node=False)
    k_scale: Optional[jnp.ndarray] = None   # [L, NB, KH, BS] f32 | None
    v_scale: Optional[jnp.ndarray] = None
    ring_k: Optional[jnp.ndarray] = None    # [Lw, S*RB, BS, KHw*D] | None
    ring_v: Optional[jnp.ndarray] = None    # [Lw, S*RB, BS, KHw*Dv]
    aux: Optional[jnp.ndarray] = None       # the model's; int32
    layer_map: Optional[tuple] = struct.field(pytree_node=False,
                                              default=None)
    state: Optional[tuple] = None           # of [S, *state_shape] | None
    conv: Optional[tuple] = None            # of [taps, S, C] | None
    ring_kv_heads: Optional[int] = struct.field(pytree_node=False,
                                                default=None)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def head_dim(self) -> int:
        """A KEY head's width (a query's), in the pool and the rings."""
        return self.k.shape[3] // self.num_kv_heads

    @property
    def v_head_dim(self) -> int:
        """A VALUE head's width: ``head_dim`` unless built otherwise."""
        return self.v.shape[3] // self.num_kv_heads

    @property
    def ring_heads(self) -> int:
        """Key/value heads of a ring row."""
        return self.ring_kv_heads or self.num_kv_heads

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def num_slots(self) -> int:
        return self.block_tables.shape[0]

    @property
    def max_blocks(self) -> int:
        return self.block_tables.shape[1]

    @property
    def max_context(self) -> int:
        return self.max_blocks * self.block_size

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def ring_blocks(self) -> int:
        """Blocks of one slot's ring of one window layer."""
        return self.ring_k.shape[1] // self.num_slots

    @property
    def ring_rows(self) -> int:
        return self.ring_blocks * self.block_size


def ring_blocks_for(window: int, block_size: int) -> int:
    """Blocks of a ring that holds a window of ``window`` positions: the
    window rounded up to whole blocks plus one block of slack. A
    one-token append at position ``n`` overwrites position ``n - R``,
    which no window ``<= R`` sees; the slack is for positions written
    ahead of the committed length (up to a block of them)."""
    return -(-window // block_size) + 1


def window_layer_map(window_layers) -> tuple:
    """Model layer -> ``(kind, index among the layers of its kind)``,
    ``kind`` ``"window"`` (a ring) or ``"full"`` (the pool), from a bool
    a layer."""
    return kind_layer_map("window" if w else "full" for w in window_layers)


def kind_layer_map(kinds) -> tuple:
    """Model layer -> ``(kind, index among the layers of its kind)`` from
    a kind a layer: ``"full"`` (blocks of the pool), ``"window"`` (a
    ring a slot), ``"state"`` (a recurrent state a slot) or ``"none"`` (a
    layer that keeps nothing: its index counts such layers and names no
    buffer)."""
    counts = {"full": 0, "window": 0, "state": 0, "none": 0}
    out = []
    for kind in kinds:
        out.append((kind, counts[kind]))
        counts[kind] += 1
    return tuple(out)


def init_paged_cache(num_layers: int, num_slots: int, num_blocks: int,
                     block_size: int, max_blocks_per_slot: int,
                     num_kv_heads: int, head_dim: int,
                     dtype=jnp.bfloat16,
                     quantized: bool = False,
                     window_layers: Optional[tuple] = None,
                     window: int = 0,
                     aux_shape: Optional[tuple] = None,
                     state_layers: Optional[tuple] = None,
                     state_shapes: Optional[tuple] = None,
                     state_dtype=jnp.float32,
                     v_head_dim: Optional[int] = None,
                     ring_kv_heads: Optional[int] = None,
                     cacheless_layers: Optional[tuple] = None
                     ) -> PagedKVCache:
    """``num_blocks`` INCLUDES the reserved null block 0, so the usable
    pool is ``num_blocks - 1`` blocks. ``quantized=True`` builds the
    int8 pool (payload dtype int8 regardless of ``dtype``) with
    all-ones scale tiles — unwritten garbage dequantizes to exact
    zeros, the same dead-memory story as the fp pool.

    ``window_layers`` (a bool a model layer) with ``window``: the layers
    marked true keep a ring of :func:`ring_blocks_for` blocks a slot
    beside the pool, which then holds the other layers only.

    ``state_layers`` (a bool a model layer) with ``state_shapes``
    (``(state_shape, (taps, channels))``, one slot's of one layer): the
    layers marked true keep a recurrent state and a convolution tail a
    slot (``state_dtype`` and ``dtype``) and no row; the pool holds the
    other layers only.

    ``v_head_dim`` (None: ``head_dim``): a value head's width where it is
    not a key head's; V's rows, in the pool and in the rings, are then
    ``heads * v_head_dim`` wide beside K's ``heads * head_dim``.
    ``ring_kv_heads`` (None: ``num_kv_heads``): the window layers' own
    key/value head count.

    ``cacheless_layers`` (a bool a model layer): the layers marked true
    keep nothing (kind ``"none"`` in the map); the pool holds a slab for
    each of the others that is no window or state layer."""
    layer_map = rings = states = None
    v_dim = head_dim if v_head_dim is None else v_head_dim
    if quantized and v_dim != head_dim:
        raise NotImplementedError(
            "int8 rows of two widths: a scale tile holds one scale a "
            "(position, head) row for K and V alike, and no kernel or "
            "writer has been tested over a pool whose K and V rows differ "
            "in width")
    if ring_kv_heads is not None and not sum(window_layers or ()):
        raise ValueError("ring_kv_heads without window layers")
    n_window = sum(window_layers) if window_layers is not None else 0
    n_state = sum(state_layers) if state_layers is not None else 0
    n_none = sum(cacheless_layers) if cacheless_layers is not None else 0
    if n_window or n_state or n_none:
        if quantized:
            raise NotImplementedError(
                "window layers' rings and state layers' states have no "
                "int8 rows or scale tiles")
        if n_window and n_state:
            raise NotImplementedError("window layers' rings beside state "
                                      "layers' states")
        marked = (window_layers if n_window else state_layers if n_state
                  else (False,) * num_layers)
        nothing = cacheless_layers or (False,) * num_layers
        if len(marked) != num_layers or len(nothing) != num_layers:
            raise ValueError(f"{len(marked)} layer kinds and "
                             f"{len(nothing)} cacheless marks for "
                             f"{num_layers} layers")
        if any(m and c for m, c in zip(marked, nothing)):
            raise ValueError("a layer marked cacheless keeps a ring or a "
                             "state")
        beside = "window" if n_window else "state"
        layer_map = kind_layer_map(
            "none" if c else beside if m else "full"
            for m, c in zip(marked, nothing))
        # a model of window (or state) layers only still has a
        # (one-layer) pool: the block tables and the null block stay
        # what they are
        num_layers = max(num_layers - n_window - n_state - n_none, 1)
    if n_window:
        rings = (n_window,
                 num_slots * ring_blocks_for(window, block_size),
                 block_size)
    if n_state:
        s_shape, (taps, channels) = state_shapes
        # one array PER layer and field: a buffer shared by two would be
        # donated twice in the serving jits
        states = (tuple(jnp.zeros((num_slots, *s_shape), state_dtype)
                        for _ in range(n_state)),
                  tuple(jnp.zeros((taps, num_slots, channels), dtype)
                        for _ in range(n_state)))
    pool = (num_layers, num_blocks, block_size)
    pool_dtype = jnp.int8 if quantized else dtype
    ring_heads = ring_kv_heads or num_kv_heads

    def rows(place, heads, width, dtype):
        """A place's rows: ``heads`` heads of ``width`` lanes side by
        side."""
        return jnp.zeros((*place, heads * width), dtype)

    def scales():
        # one array PER field: aliasing k_scale/v_scale to the same
        # buffer would donate it twice in the serving jits
        if not quantized:
            return None
        return jnp.ones(
            (num_layers, num_blocks, num_kv_heads, block_size),
            jnp.float32)

    return PagedKVCache(
        k=rows(pool, num_kv_heads, head_dim, pool_dtype),
        v=rows(pool, num_kv_heads, v_dim, pool_dtype),
        block_tables=jnp.zeros((num_slots, max_blocks_per_slot),
                               jnp.int32),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        num_kv_heads=num_kv_heads, k_scale=scales(), v_scale=scales(),
        ring_k=(None if rings is None
                else rows(rings, ring_heads, head_dim, dtype)),
        ring_v=(None if rings is None
                else rows(rings, ring_heads, v_dim, dtype)),
        aux=None if aux_shape is None else jnp.zeros(aux_shape, jnp.int32),
        layer_map=layer_map, ring_kv_heads=ring_kv_heads,
        state=None if states is None else states[0],
        conv=None if states is None else states[1])


def with_state_layer(cache: PagedKVCache, i: int, state,
                     conv) -> PagedKVCache:
    """``cache`` with state layer ``i``'s two buffers replaced."""
    return cache.replace(
        state=cache.state[:i] + (state,) + cache.state[i + 1:],
        conv=cache.conv[:i] + (conv,) + cache.conv[i + 1:])


def _quant_rows(cache: PagedKVCache, x: jnp.ndarray):
    """Writer-side quantization seam: for an int8 pool, quantize
    ``[..., KH, D]`` per (position, head) row along D → (int8 payload,
    scales ``[..., KH]``); for an fp pool, cast and carry no scales.
    Every paged writer routes through here so the write-side scale
    semantics cannot drift between the prompt/append/chunk/verify
    paths."""
    if cache.k_scale is None:
        return x.astype(cache.k.dtype), None
    q, s = quantize_int8(x, -1)
    return q, s[..., 0]


def _head_rows(cache: PagedKVCache, x: jnp.ndarray) -> jnp.ndarray:
    """Gathered pool rows ``[..., KH*D]`` as heads ``[..., KH, D]``."""
    return x.reshape(*x.shape[:-1], cache.num_kv_heads, -1)


@scoped("kv_write")
def paged_write_prompt(cache: PagedKVCache, layer: int, k: jnp.ndarray,
                       v: jnp.ndarray, slot: jnp.ndarray) -> PagedKVCache:
    """Prefill: scatter one prompt's ``[T, H, D]`` k/v into ``slot``'s
    blocks at logical positions ``0..T-1`` (T divisible by block_size).

    Positions beyond the live length hold right-pad garbage — exactly the
    dense :func:`write_prompt` invariant: masked by attention, overwritten
    by later appends. Lengths are NOT set here (all layers write the same
    prompt); the caller pins ``lengths[slot]`` once."""
    BS = cache.block_size
    T = k.shape[0]
    nb = T // BS
    idx = jax.lax.dynamic_slice_in_dim(cache.block_tables, slot, 1, 0
                                       )[0, :nb]            # [nb]
    return _scatter_blocks(cache, layer, idx, k, v)


def _scatter_blocks(cache: PagedKVCache, layer: int, idx: jnp.ndarray,
                    k: jnp.ndarray, v: jnp.ndarray) -> PagedKVCache:
    """Whole-block scatter shared by the prompt and chunk writers:
    ``[nb*BS, H, D]`` k/v into pool blocks ``idx [nb]`` (quantizing per
    (position, head) row when the pool is int8 — the scale tile scatter
    rides the same indices)."""
    BS = cache.block_size
    nb = idx.shape[0]
    qk, sk = _quant_rows(cache, k)
    qv, sv = _quant_rows(cache, v)
    # the NEW rows take the pool's layout (heads side by side in a
    # position's row); the pool itself is never reshaped
    newk = cache.k.at[layer, idx].set(qk.reshape(nb, BS, -1))
    newv = cache.v.at[layer, idx].set(qv.reshape(nb, BS, -1))
    out = cache.replace(k=newk, v=newv)
    if sk is not None:
        # [T, KH] -> per-block [nb, KH, BS] scale tiles
        KH = k.shape[1]
        skt = sk.reshape(nb, BS, KH).transpose(0, 2, 1)
        svt = sv.reshape(nb, BS, KH).transpose(0, 2, 1)
        out = out.replace(
            k_scale=cache.k_scale.at[layer, idx].set(skt),
            v_scale=cache.v_scale.at[layer, idx].set(svt))
    return out


@scoped("kv_write")
def paged_append_token(cache: PagedKVCache, layer: int, k: jnp.ndarray,
                       v: jnp.ndarray) -> PagedKVCache:
    """Decode: append one token's ``[S, H, D]`` k/v at ``lengths[s]`` for
    every slot. Idle slots (all-zero table, length 0) write into the null
    block. Lengths advance once per step via :func:`paged_advance`."""
    BS = cache.block_size
    pos = cache.lengths                      # [S]
    blk = jnp.take_along_axis(cache.block_tables,
                              (pos // BS)[:, None], axis=1)[:, 0]  # [S]
    off = pos % BS
    return _scatter_positions(cache, layer, blk, off, k, v)


def _scatter_positions(cache: PagedKVCache, layer: int, blk: jnp.ndarray,
                       off: jnp.ndarray, k: jnp.ndarray,
                       v: jnp.ndarray) -> PagedKVCache:
    """Per-position scatter shared by the append and verify writers:
    k/v ``[..., H, D]`` with leading dims matching ``blk``/``off``
    (``[S]`` or ``[S, K]``), quantizing rows when the pool is int8.
    The scale scatter uses the same (block, offset) pairs — mixed
    advanced/slice indexing puts the advanced dims first, which is
    exactly the ``[..., KH]`` shape :func:`_quant_rows` returns."""
    qk, sk = _quant_rows(cache, k)
    qv, sv = _quant_rows(cache, v)
    newk = cache.k.at[layer, blk, off].set(qk.reshape(*blk.shape, -1))
    newv = cache.v.at[layer, blk, off].set(qv.reshape(*blk.shape, -1))
    out = cache.replace(k=newk, v=newv)
    if sk is not None:
        out = out.replace(
            k_scale=cache.k_scale.at[layer, blk, :, off].set(sk),
            v_scale=cache.v_scale.at[layer, blk, :, off].set(sv))
    return out


def ring_newest_position(newest, rows: int):
    """``[..., R]``: the position each row of a ring of ``R`` rows holds
    once position ``newest [...]`` has been written: the largest ``p <=
    newest`` with ``p = r (mod R)``. Negative: the row was never written
    (a context shorter than the ring)."""
    r = jnp.arange(rows, dtype=jnp.int32)
    return newest[..., None] - jnp.mod(newest[..., None] - r, rows)


@scoped("kv_write")
def ring_write_prompt(cache: PagedKVCache, ring_layer: int, k: jnp.ndarray,
                      v: jnp.ndarray, slot: jnp.ndarray,
                      length: jnp.ndarray) -> PagedKVCache:
    """Prefill of a window layer: of one prompt's ``[T, H, D]`` k/v
    (``length`` live tokens) the LAST ``ring_rows`` positions go into
    ``slot``'s ring, position ``p`` at row ``p mod ring_rows``; the rest
    of the prompt is never stored. A row whose position would be
    negative keeps the prompt's first row and is masked by position when
    read."""
    RB, R = cache.ring_blocks, cache.ring_rows
    src = jnp.clip(ring_newest_position(length.astype(jnp.int32) - 1, R),
                   0, k.shape[0] - 1)

    def put(ring, x):
        rows = x.reshape(x.shape[0], -1)[src].astype(ring.dtype)
        return jax.lax.dynamic_update_slice(
            ring, rows.reshape(1, RB, cache.block_size, -1),
            (ring_layer, slot * RB, 0, 0))
    return cache.replace(ring_k=put(cache.ring_k, k),
                         ring_v=put(cache.ring_v, v))


@scoped("kv_write")
def ring_append_token(cache: PagedKVCache, ring_layer: int, k: jnp.ndarray,
                      v: jnp.ndarray) -> PagedKVCache:
    """Decode of a window layer: one token's ``[S, H, D]`` k/v at row
    ``lengths[s] mod ring_rows`` of every slot's ring. An idle slot
    (length 0) writes row 0 of its own ring, which its next prefill
    overwrites."""
    BS, RB = cache.block_size, cache.ring_blocks
    row = jnp.mod(cache.lengths, cache.ring_rows)
    blk = jnp.arange(cache.num_slots, dtype=jnp.int32) * RB + row // BS
    off = row % BS
    S = k.shape[0]
    return cache.replace(
        ring_k=cache.ring_k.at[ring_layer, blk, off].set(
            k.reshape(S, -1).astype(cache.ring_k.dtype)),
        ring_v=cache.ring_v.at[ring_layer, blk, off].set(
            v.reshape(S, -1).astype(cache.ring_v.dtype)))


@scoped("kv_write")
def paged_write_tokens(cache: PagedKVCache, layer: int, k: jnp.ndarray,
                       v: jnp.ndarray) -> PagedKVCache:
    """Speculative verify: write K tokens' ``[S, K, H, D]`` k/v for
    EVERY slot at logical positions ``lengths[s]..lengths[s]+K-1``
    through the block tables. Lengths are NOT advanced — the caller
    commits only the accepted prefix by advancing per-slot lengths;
    rejected positions stay as garbage beyond ``lengths`` (masked by
    attention, overwritten by the next round's writes) — the paged
    analog of :func:`write_chunk`, and :func:`paged_append_token`
    generalized to K positions (K=1 writes the identical bytes).

    The span may straddle a block boundary mid-write (positions are not
    block-aligned, unlike :func:`paged_write_chunk`): each position
    resolves its own table entry. A position whose block index runs
    past the table itself (a wedged slot decoding beyond its budget)
    redirects to the reserved null block 0 instead of letting the
    gather clamp silently target the table's LAST live entry."""
    BS = cache.block_size
    K = k.shape[1]
    MB = cache.max_blocks
    pos = cache.lengths[:, None] + jnp.arange(K)[None, :]     # [S, K]
    pb = pos // BS
    blk = jnp.take_along_axis(cache.block_tables,
                              jnp.clip(pb, 0, MB - 1), axis=1)
    blk = jnp.where(pb < MB, blk, 0)       # overshoot -> null block
    off = pos % BS
    return _scatter_positions(cache, layer, blk, off, k, v)


@scoped("kv_write")
def paged_write_chunk(cache: PagedKVCache, layer: int, k: jnp.ndarray,
                      v: jnp.ndarray, slot: jnp.ndarray,
                      start: jnp.ndarray) -> PagedKVCache:
    """Chunked prefill: scatter a C-token chunk's ``[C, H, D]`` k/v into
    ``slot``'s blocks at logical positions ``start..start+C-1``. Both C
    and ``start`` must be block-aligned (the chunk loop guarantees it:
    chunks start at the block-aligned cached-prefix boundary and step by
    a block-multiple chunk size), so the scatter is whole blocks — the
    same shape contract as :func:`paged_write_prompt`, shifted by a
    traced ``start``. Positions past the live prompt length hold
    right-pad garbage (masked, later overwritten); table entries past
    the allocated span are 0, so overshoot spills into the null block."""
    BS = cache.block_size
    C = k.shape[0]
    nb = C // BS
    row = jax.lax.dynamic_slice_in_dim(cache.block_tables, slot, 1, 0)[0]
    # pad with null-block entries so a chunk window running past the
    # table tail spills into block 0 — dynamic_slice would otherwise
    # CLAMP the start index and silently shift the write window onto
    # earlier (possibly shared) blocks
    row = jnp.concatenate([row, jnp.zeros((nb,), jnp.int32)])
    idx = jax.lax.dynamic_slice_in_dim(row, start // BS, nb, 0)   # [nb]
    return _scatter_blocks(cache, layer, idx, k, v)


@scoped("kv_read")
def paged_gather_slot_kv(cache: PagedKVCache, layer: int, slot: jnp.ndarray):
    """Materialize ONE slot's cache ``[1, max_context, H, D]`` through
    its block table — the chunk-attends-over-table gather (chunked
    prefill needs only the prefilling slot's context, not the whole
    pool's num_slots rows like :func:`paged_gather_kv`). An int8 pool
    dequantizes at the gather (f32 out — the fused multiply is free
    next to the gather's HBM traffic)."""
    row = jax.lax.dynamic_slice_in_dim(cache.block_tables, slot, 1, 0)[0]
    k = _head_rows(cache, cache.k[layer][row])        # [MB, BS, H, D]
    v = _head_rows(cache, cache.v[layer][row])
    if cache.k_scale is not None:
        # scale tiles [MB, KH, BS] -> [MB, BS, KH, 1] against the pool
        k = dequantize_int8(
            k, cache.k_scale[layer][row].transpose(0, 2, 1)[..., None])
        v = dequantize_int8(
            v, cache.v_scale[layer][row].transpose(0, 2, 1)[..., None])
    return (k.reshape(1, cache.max_context, *k.shape[2:]),
            v.reshape(1, cache.max_context, *v.shape[2:]))


def prefix_block_hashes(prompt, block_size: int) -> list:
    """Chain hashes for every FULL block of a prompt: block i's hash is
    ``sha256(hash_{i-1} || tokens[i*BS:(i+1)*BS])`` — a block matches
    only under its entire preceding prefix, which is what makes reuse
    position-safe (rotary k/v, learned positions and ALiBi all depend
    on absolute position, and a chained full-prefix match pins it).
    sha256 because a collision would silently serve another prompt's
    context. The ids go in as int64 bytes: ~1 ms for a 33k-token
    prompt, paid at ``Scheduler.submit``, off the step loop."""
    ids = np.asarray(prompt, dtype=np.int64)
    out, prev = [], b""
    for i in range(len(ids) // block_size):
        h = hashlib.sha256(
            prev + ids[i * block_size:(i + 1) * block_size].tobytes()
        ).digest()
        out.append(h)
        prev = h
    return out


@scoped("kv_read")
def paged_gather_kv(cache: PagedKVCache, layer: int):
    """Materialize per-slot caches ``[S, max_context, H, D]`` through the
    block tables — the pure-JAX decode fallback (CPU / ALiBi / windowed).
    Gathered position j is logical position j, so downstream masked
    attention is bit-identical to the dense-cache path. An int8 pool
    dequantizes at the gather (f32 out)."""
    S, MB = cache.block_tables.shape
    k = _head_rows(cache, cache.k[layer][cache.block_tables])
    v = _head_rows(cache, cache.v[layer][cache.block_tables])  # [S,MB,BS,H,D]
    if cache.k_scale is not None:
        # scale tiles [S, MB, KH, BS] -> [S, MB, BS, KH, 1]
        ks = cache.k_scale[layer][cache.block_tables]
        vs = cache.v_scale[layer][cache.block_tables]
        k = dequantize_int8(k, ks.transpose(0, 1, 3, 2)[..., None])
        v = dequantize_int8(v, vs.transpose(0, 1, 3, 2)[..., None])
    return (k.reshape(S, cache.max_context, *k.shape[3:]),
            v.reshape(S, cache.max_context, *v.shape[3:]))


def paged_advance(cache, active: jnp.ndarray):
    """Advance live slots' lengths by one; idle slots stay where they
    are (a K/V pool's idle slots are pinned at 0 over an all-null table,
    so their appends keep landing in the null block; a latent pool's
    write nothing). Either kind of pool."""
    return cache.replace(
        lengths=cache.lengths + active.astype(jnp.int32))


# ------------------------------------------------------------ latent pool
# Latent attention (MLA) caches ONE row per token per attention: the
# normed compressed latent and the shared rotary key, ``[c_kv ; k_rope]``
# (576 values at the published widths), not K and V per head. A model may
# hold several attentions a layer (a shortcut-connected double block has
# two). The pool is ONE BUFFER PER ATTENTION, ``rows[i] [NB, W, BS]``
# (a block's positions on the last, lane, dim: W = 576 is not a multiple
# of 128 lanes and BS = 128 is; ops/pallas/latent_decode_attention.py),
# under the same block tables, lengths, null block and allocator as
# :class:`PagedKVCache`: no program ever cuts one attention's rows out of
# a stacked ``[L, NB, ...]`` array (the K/V pool avoids that cut its own
# way: its kernels take the stacked array whole), and an append is an
# in-place update of a donated buffer.


@struct.dataclass
class LatentPagedCache:
    """Paged decode workspace of a latent-attention model.

    rows: one ``[num_blocks, W, block_size]`` pool per attention
    sub-block, in the order the model runs them (layer-major); row
    ``t`` of a block is ``rows[i][block, :, t]``.
    block_tables / lengths: as :class:`PagedKVCache`.
    aux: an int32 array that belongs to the MODEL (shape
    ``cfg.aux_shape``): its programs may accumulate into it as they run
    and the server fetches it with the sampled tokens. The cache never
    reads it and does not know what it holds; it rides here because the
    cache is the one donated value every serving program threads
    through, so it costs no extra output, fetch or sync."""
    rows: tuple                # of [NB, W, BS]
    block_tables: jnp.ndarray  # [S, MB] int32
    lengths: jnp.ndarray       # [S] int32
    aux: jnp.ndarray           # the model's; int32

    @property
    def block_size(self) -> int:
        return self.rows[0].shape[2]

    @property
    def num_blocks(self) -> int:
        return self.rows[0].shape[0]


def init_latent_paged_cache(num_attentions: int, num_slots: int,
                            num_blocks: int, block_size: int,
                            max_blocks_per_slot: int, width: int,
                            aux_shape=(1, 1),
                            dtype=jnp.bfloat16) -> LatentPagedCache:
    """``num_blocks`` includes the reserved null block 0, as in
    :func:`init_paged_cache`."""
    return LatentPagedCache(
        rows=tuple(jnp.zeros((num_blocks, width, block_size), dtype)
                   for _ in range(num_attentions)),
        block_tables=jnp.zeros((num_slots, max_blocks_per_slot), jnp.int32),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        aux=jnp.zeros(aux_shape, jnp.int32))


def with_latent_rows(cache: LatentPagedCache, idx: int,
                     new) -> LatentPagedCache:
    """``cache`` with attention ``idx``'s pool replaced."""
    return cache.replace(
        rows=cache.rows[:idx] + (new,) + cache.rows[idx + 1:])


@scoped("latent_write")
def latent_write_prompt(cache: LatentPagedCache, idx: int,
                        rows: jnp.ndarray, slot) -> LatentPagedCache:
    """Prefill: scatter one prompt's ``[T, W]`` rows of attention ``idx``
    into ``slot``'s blocks at positions ``0..T-1`` (T a multiple of the
    block size). The same right-pad invariant as
    :func:`paged_write_prompt`; lengths are pinned by the caller."""
    BS = cache.block_size
    nb = rows.shape[0] // BS
    blocks = jax.lax.dynamic_slice_in_dim(cache.block_tables, slot, 1,
                                          0)[0, :nb]
    pool = cache.rows[idx]
    return with_latent_rows(cache, idx, pool.at[blocks].set(
        jnp.swapaxes(rows.reshape(nb, BS, -1), 1, 2).astype(pool.dtype)))


@scoped("latent_write")
def latent_write_chunk(cache: LatentPagedCache, idx: int, rows: jnp.ndarray,
                       slot, start) -> LatentPagedCache:
    """Chunked prefill: scatter a chunk's ``[C, W]`` rows of attention
    ``idx`` into ``slot``'s blocks at positions ``start .. start + C -
    1``. ``C`` and ``start`` are block-aligned, as for
    :func:`paged_write_chunk` (whole blocks; a window past the table's
    tail, and table entries past the allocated span, spill into the null
    block)."""
    BS = cache.block_size
    nb = rows.shape[0] // BS
    row = jax.lax.dynamic_slice_in_dim(cache.block_tables, slot, 1, 0)[0]
    row = jnp.concatenate([row, jnp.zeros((nb,), jnp.int32)])
    blocks = jax.lax.dynamic_slice_in_dim(row, start // BS, nb, 0)
    pool = cache.rows[idx]
    return with_latent_rows(cache, idx, pool.at[blocks].set(
        jnp.swapaxes(rows.reshape(nb, BS, -1), 1, 2).astype(pool.dtype)))


@scoped("latent_write")
def latent_append_token(cache: LatentPagedCache, idx: int,
                        rows: jnp.ndarray, active) -> LatentPagedCache:
    """Decode: append one token's ``[S, W]`` row of attention ``idx`` at
    ``lengths[s]`` for every active slot; any other slot writes nothing.
    Lengths advance once a step (:func:`paged_advance`). The XLA
    scatter: the decode path off the TPU, and the oracle of the latent
    decode kernel, which appends on one
    (``ops/pallas/latent_decode_attention.py``)."""
    pool = cache.rows[idx]
    BS = cache.block_size
    pos = cache.lengths
    blk = jnp.take_along_axis(cache.block_tables, (pos // BS)[:, None],
                              axis=1)[:, 0]
    # past the pool: dropped
    blk = jnp.where(active, blk, cache.num_blocks)
    return with_latent_rows(cache, idx, pool.at[blk, :, pos % BS].set(
        rows.astype(pool.dtype), mode="drop"))


# ------------------------------------------------------------- state pool
# A retention (gated linear attention) layer caches no row per token: a
# sequence's cache is a STATE of fixed size a layer, which every decode
# step reads, updates and writes back whatever the context length
# (ops/pallas/power_retention.py: ``S [KH, R, dv, d]`` and ``z [KH, Rz,
# d]``, float32). The pool is one slot-major buffer a layer for each, so
# no program cuts a layer out of a stacked array and a prefill writes one
# slot of a donated buffer in place. There are no blocks and no block
# tables: what binds admission is a free slot. ``lengths`` are kept for
# rotary positions only.


@struct.dataclass
class RecurrentStateCache:
    """Decode workspace of a model whose layers keep a recurrent state.

    S / z: one buffer a layer, ``[slots, KH, R, dv, d]`` and ``[slots,
    KH, Rz, d]`` float32. A retired slot's state is overwritten by the
    next prefill and never read.
    lengths: ``[slots]`` int32, the tokens each slot has consumed (the
    next rotary position); idle slots stay at 0.
    aux: the MODEL's int32 counters, as :class:`LatentPagedCache`."""
    S: tuple                   # of [slots, KH, R, dv, d]
    z: tuple                   # of [slots, KH, Rz, d]
    lengths: jnp.ndarray       # [slots] int32
    aux: jnp.ndarray           # the model's; int32


def init_recurrent_state_cache(num_layers: int, num_slots: int,
                               s_shape: tuple, z_shape: tuple,
                               aux_shape=(1, 1),
                               dtype=jnp.float32) -> RecurrentStateCache:
    """``s_shape`` / ``z_shape``: one slot's state of one layer."""
    return RecurrentStateCache(
        S=tuple(jnp.zeros((num_slots, *s_shape), dtype)
                for _ in range(num_layers)),
        z=tuple(jnp.zeros((num_slots, *z_shape), dtype)
                for _ in range(num_layers)),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        aux=jnp.zeros(aux_shape, jnp.int32))


def with_layer_state(cache: RecurrentStateCache, layer: int, S,
                     z) -> RecurrentStateCache:
    return cache.replace(
        S=cache.S[:layer] + (S,) + cache.S[layer + 1:],
        z=cache.z[:layer] + (z,) + cache.z[layer + 1:])


def pool_arrays(cache) -> tuple:
    """The device arrays that make up a pool's payload, whichever kind
    of pool it is (memory accounting reads their sizes)."""
    if isinstance(cache, RecurrentStateCache):
        return tuple(cache.S) + tuple(cache.z)
    if isinstance(cache, LatentPagedCache):
        return tuple(cache.rows)
    beside = () if cache.ring_k is None else (cache.ring_k, cache.ring_v)
    if cache.state is not None:
        beside += tuple(cache.state) + tuple(cache.conv)
    if cache.k_scale is None:
        return (cache.k, cache.v) + beside
    return (cache.k, cache.v, cache.k_scale, cache.v_scale) + beside


# ------------------------------------------------------------- host tier
# ZeRO-Offload for the serving pool (PAPER.md §7 mapped to paged blocks):
# a demoted block's payload (k/v slabs across all layers, plus scale
# tiles for an int8 pool) moves to host RAM keyed by its chain hash;
# the device block recycles. A later match_prefix hit on the hash swaps
# the payload back into a freshly allocated block through the jitted
# staging writer below — ONE traced signature per pool geometry (the
# block id is a traced scalar), so tier membership never retraces the
# serving programs.


@jax.jit
def _read_block_impl(cache: PagedKVCache, block):
    def cut(a):
        return jax.lax.dynamic_slice_in_dim(a, block, 1, 1)[:, 0]

    if cache.k_scale is not None:
        return (cut(cache.k), cut(cache.v),
                cut(cache.k_scale), cut(cache.v_scale))
    return cut(cache.k), cut(cache.v)


def paged_read_block(cache: PagedKVCache, block: int) -> Dict[str, Any]:
    """Device→host copy of one pool block's payload across all layers:
    ``{"k": [L, BS, KH*D], "v": ..., ("k_scale"/"v_scale": [L, KH, BS])}``
    as numpy arrays, rows in the pool's own layout (the demotion copy —
    ``np.asarray`` forces the transfer, so by return the content is
    host-durable and the device block is safe to recycle). The gather
    is jitted with the block id as TRACED data — the same
    one-executable-per-pool-geometry contract as :func:`paged_swap_in`,
    so demotions never grow the compile cache however many distinct
    blocks tier out."""
    out = _read_block_impl(cache, jnp.int32(block))
    if len(out) == 4:
        return {"k": np.asarray(out[0]), "v": np.asarray(out[1]),
                "k_scale": np.asarray(out[2]),
                "v_scale": np.asarray(out[3])}
    return {"k": np.asarray(out[0]), "v": np.asarray(out[1])}


@functools.partial(jax.jit, donate_argnums=(0,))
def _swap_in_impl(cache: PagedKVCache, block, k, v, ks, vs):
    newk = jax.lax.dynamic_update_slice(cache.k, k[:, None],
                                        (0, block, 0, 0))
    newv = jax.lax.dynamic_update_slice(cache.v, v[:, None],
                                        (0, block, 0, 0))
    out = cache.replace(k=newk, v=newv)
    if ks is not None:
        out = out.replace(
            k_scale=jax.lax.dynamic_update_slice(
                cache.k_scale, ks[:, None], (0, block, 0, 0)),
            v_scale=jax.lax.dynamic_update_slice(
                cache.v_scale, vs[:, None], (0, block, 0, 0)))
    return out


def paged_swap_in(cache: PagedKVCache, block: int,
                  payload: Dict[str, Any]) -> PagedKVCache:
    """Host→device copy of a demoted payload into pool ``block``: the
    staging write is a single jitted donated scatter (one executable
    per pool geometry — ``block`` rides as a traced scalar), so
    swap-ins never grow the compile cache however many blocks cycle
    through the tier."""
    return _swap_in_impl(cache, jnp.int32(block),
                         jnp.asarray(payload["k"]),
                         jnp.asarray(payload["v"]),
                         (jnp.asarray(payload["k_scale"])
                          if "k_scale" in payload else None),
                         (jnp.asarray(payload["v_scale"])
                          if "v_scale" in payload else None))


class HostKVTier:
    """Host-RAM residency for demoted KV blocks, keyed by chain hash.

    Pure host storage + bookkeeping: the BlockAllocator decides WHEN to
    demote/swap in (its ``on_demote``/``on_swap_in`` callbacks do the
    copies — the server owns the device arrays), this class only holds
    payloads. Insertion order doubles as host-LRU: past ``max_blocks``
    the oldest payload drops for good (its hash index is forgotten by
    the allocator-side miss, so a later identical prefix re-prefills,
    exactly like a plain eviction).

    ``put`` on a hash that is already host-resident raises — a double
    demote means two device blocks claimed the same chain hash, which
    the first-writer-wins ``register_prefix`` contract rules out; going
    quiet here would mask refcount corruption."""

    def __init__(self, max_blocks: Optional[int] = None):
        if max_blocks is not None and max_blocks < 1:
            raise ValueError(
                f"host tier max_blocks must be >= 1 (or None for "
                f"unbounded), got {max_blocks}")
        self.max_blocks = max_blocks
        self._store: "OrderedDict[bytes, Dict[str, Any]]" = OrderedDict()
        self._block_nbytes = 0    # payload size, learned at first put
        self.swap_outs = 0        # payloads demoted into the tier
        self.swap_ins = 0         # payloads promoted back to device
        self.dropped = 0          # host-LRU drops (content gone for good)
        self.superseded = 0       # payloads purged by device re-registration

    def __len__(self) -> int:
        return len(self._store)

    @property
    def host_bytes(self) -> int:
        """Bytes parked in host RAM (every payload is the same size —
        one pool block across all layers)."""
        return len(self._store) * self._block_nbytes

    @property
    def block_nbytes(self) -> int:
        """Payload bytes of ONE tiered block (0 until the first put
        teaches the tier its geometry) — the cost ledger prices
        swap-in traffic with this (swap-ins x block_nbytes)."""
        return self._block_nbytes

    def has(self, h: bytes) -> bool:
        return h in self._store

    def put(self, h: bytes, payload: Dict[str, Any]) -> None:
        if h in self._store:
            raise ValueError(
                "double demote: chain hash already host-resident — two "
                "device blocks claimed the same prefix hash")
        if not self._block_nbytes:
            self._block_nbytes = sum(int(a.nbytes)
                                     for a in payload.values())
        self._store[h] = payload
        self.swap_outs += 1
        while (self.max_blocks is not None
               and len(self._store) > self.max_blocks):
            self._store.popitem(last=False)
            self.dropped += 1

    def take(self, h: bytes) -> Dict[str, Any]:
        """Pop one payload for swap-in (the content becomes device-
        resident again under a registered hash; keeping a host copy
        would let the two go stale against each other)."""
        payload = self._store.pop(h)
        self.swap_ins += 1
        return payload

    def discard(self, h: bytes) -> bool:
        """Drop a host payload that just became REDUNDANT — the same
        hash re-registered device-side (a bounded tier's capacity drop
        can strand a descendant hash host-resident after its ancestor
        dropped; the re-prefilled chain then re-registers it, and
        without this purge the block's NEXT demotion would trip the
        double-demote alarm on perfectly healthy state). Returns True
        when a payload was dropped."""
        if self._store.pop(h, None) is None:
            return False
        self.superseded += 1
        return True


class BlockAllocator:
    """Host-side refcounted free-list over pool blocks 1..num_blocks-1
    (block 0 is the reserved null block). The analog of the reference's
    free-HBM workspace bookkeeping (inference_context.h), except
    recycling is per-block: an EOS'd sequence's blocks return here and
    are re-handed to a queued request without any device reallocation or
    retrace.

    Prefix caching (vLLM-style automatic block reuse): a FULL block that
    covers an immutable block-aligned prompt prefix can be registered
    under its chain hash (hash of its token span, chained on the
    previous block's hash — see :meth:`register_prefix`). A later
    request whose prompt shares that exact prefix takes the block by
    refcount (:meth:`match_prefix`) instead of allocating + prefilling
    it. Released cached blocks (refcount 0) are NOT returned to the
    free list — they park in an LRU of evictable blocks and are evicted
    (hash dropped, memory reused) only when an allocation outruns the
    free list. Copy-on-write is never needed: only full, never-again-
    written prefix blocks are ever registered (decode appends at
    ``lengths >= prompt_len``, beyond every cached block).

    The free list is a stack (pop → low ids) with a set shadow for O(1)
    membership, so ``release`` stays O(len(blocks)) — the r5 linear
    ``b in self._free`` scan made it O(n²) per sequence."""

    def __init__(self, num_blocks: int, enable_prefix_caching: bool = False,
                 accountant=None, host_tier: Optional[HostKVTier] = None):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 pool blocks (1 usable + the null block), "
                f"got {num_blocks}")
        if host_tier is not None and not enable_prefix_caching:
            raise ValueError(
                "host offload tiers demoted PREFIX blocks — it needs "
                "enable_prefix_caching (a hashless block has no "
                "identity to swap back in under)")
        self.num_blocks = num_blocks
        self.enable_prefix_caching = enable_prefix_caching
        # host offload (docs/serving.md "KV quantization & host
        # tiering"): when set, an LRU pop DEMOTES the parked block's
        # payload to host RAM instead of destroying it, and a
        # match_prefix hit on a demoted hash swaps it back in. The
        # copies are the owner's (the server holds the device arrays):
        # on_demote(block, hash) must make the payload host-durable
        # before returning, on_swap_in(block, payload) must write the
        # already-reserved payload into the freshly allocated block.
        # Until both callbacks are bound, demotion falls back to plain
        # eviction — never silent data teleportation.
        self.host_tier = host_tier
        self.on_demote = None
        self.on_swap_in = None
        self.demotions = 0     # LRU pops that preserved content on host
        self.swap_ins = 0      # host hits promoted back to device
        # pool lifetime/fragmentation accounting (telemetry/memory.py
        # KVPoolAccountant) or None — every hook sits behind a None
        # check, so an unaccounted allocator costs nothing extra
        self.accountant = accountant
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() -> low ids
        self._free_set = set(self._free)
        self._refcount: Dict[int, int] = {}       # live blocks only
        # prefix cache index: chain hash <-> block id, plus the LRU of
        # evictable (refcount-0 but content-retained) cached blocks in
        # release order — eviction pops the oldest
        self._hash_to_block: Dict[bytes, int] = {}
        self._block_hash: Dict[int, bytes] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # blocks withheld from the free budget (fault injection / tests
        # simulating pool pressure — telemetry/faultinject.py); never
        # handed out while reserved
        self.reserved_blocks = 0
        # observer for LRU evictions (the scheduler counts them + drops
        # a ring event: the first rung of the degradation ladder must be
        # visible, not silent)
        self.on_evict = None
        self.evictions = 0

    def set_reserved(self, n: int) -> None:
        """Withhold ``n`` blocks from the free budget (famine
        injection). Already-live blocks are unaffected — the squeeze
        lands on future admissions, exactly like real pressure."""
        if n < 0 or n > self.usable_blocks:
            raise ValueError(
                f"reserved blocks must be in [0, {self.usable_blocks}], "
                f"got {n}")
        self.reserved_blocks = int(n)

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: immediately free + evictable cached,
        minus any fault-injected reservation."""
        return max(
            0, len(self._free) + len(self._lru) - self.reserved_blocks)

    @property
    def usable_blocks(self) -> int:
        """Total pool capacity (excludes the reserved null block)."""
        return self.num_blocks - 1

    @property
    def cached_blocks(self) -> int:
        """Blocks currently holding a reusable hashed prefix (resident
        shared + evictable LRU)."""
        return len(self._hash_to_block)

    @property
    def live_blocks(self) -> int:
        """DISTINCT blocks held by resident sequences — a shared prefix
        block counts once however many sequences hold it, so
        ``live + free == usable`` always."""
        return len(self._refcount)

    def _pop_free(self) -> int:
        if self._free:
            b = self._free.pop()
            self._free_set.discard(b)
            return b
        # free list dry: pop the least-recently-released cached block.
        # With a host tier armed this is a DEMOTION — the payload moves
        # to host RAM under its chain hash and a later match_prefix hit
        # swaps it back — and it runs during admission's allocation,
        # i.e. BEFORE the server's preemption rung ever fires: famine
        # demotes coldest-parked blocks first. Without a tier the
        # content is gone for good (the hash index forgets it), so a
        # later identical prefix re-prefills and re-registers.
        b, _ = self._lru.popitem(last=False)
        h = self._block_hash.get(b)
        if (h is not None and self.host_tier is not None
                and self.on_demote is not None):
            self._drop_hash(b)
            self.on_demote(b, h)   # device->host, durable on return
            self.demotions += 1
            if self.accountant is not None:
                self.accountant.on_demote(b)
        else:
            self._drop_hash(b)
            self.evictions += 1
            if self.accountant is not None:
                self.accountant.on_evict(b)
            if self.on_evict is not None:
                self.on_evict(b)
        return b

    def _drop_hash(self, b: int) -> None:
        h = self._block_hash.pop(b, None)
        if h is not None and self._hash_to_block.get(h) == b:
            del self._hash_to_block[h]

    def allocate(self, n: int):
        """``n`` fresh block ids (refcount 1 each), or None (caller
        queues) when even eviction cannot cover the span."""
        if n > self.free_blocks:
            if self.accountant is not None:
                # famine: freeze the allocator state into the event
                # ring (once per episode — re-armed by the next
                # successful allocation); fragmentation refreshed so
                # the frozen snapshot is current, not Nth-transition
                # stale
                self.accountant.update_fragmentation(self._free_set)
                self.accountant.on_famine(n, self.famine_state())
            return None
        out = [self._pop_free() for _ in range(n)]
        for b in out:
            self._refcount[b] = 1
        if self.accountant is not None:
            for b in out:
                self.accountant.on_acquire(b)
            self.accountant.on_alloc_ok()
        return out

    def famine_state(self) -> dict:
        """JSON-able allocator state for the famine ring event."""
        return {
            "free_list": len(self._free),
            "evictable_lru": len(self._lru),
            "live_blocks": len(self._refcount),
            "cached_blocks": len(self._hash_to_block),
            "reserved_blocks": self.reserved_blocks,
            "usable_blocks": self.usable_blocks,
            "host_blocks": (len(self.host_tier)
                            if self.host_tier is not None else 0),
        }

    @property
    def free_ids(self):
        """Immediately-free block ids (the free list proper, evictable
        LRU excluded) — the fragmentation gauge's input."""
        return tuple(self._free_set)

    def release(self, blocks) -> None:
        """Drop one reference per block. A block reaching refcount 0
        returns to the free list — unless it holds a registered prefix,
        in which case it parks in the evictable LRU (content retained
        for future :meth:`match_prefix` hits, memory reclaimable)."""
        self._drop_refs(blocks, rollback=False)

    def _drop_refs(self, blocks, rollback: bool) -> None:
        """The refcount-decrement / park-or-free invariant, in ONE
        place (release and rollback differ only in which accounting
        hook fires at refcount 0 — duplicating the loop would leave
        the free-list bookkeeping to drift apart by hand)."""
        for b in blocks:
            if b == 0:
                raise ValueError("block 0 is the reserved null block")
            if b in self._free_set or b in self._lru:
                raise ValueError(f"double free of block {b}")
            ref = self._refcount.get(b, 0)
            if ref <= 0:
                raise ValueError(f"double free of block {b}")
            if ref > 1:
                self._refcount[b] = ref - 1
                continue
            del self._refcount[b]
            parked = b in self._block_hash
            if parked:
                self._lru[b] = None
            else:
                self._free.append(b)
                self._free_set.add(b)
            if self.accountant is not None:
                if rollback:
                    self.accountant.on_rollback(b)
                else:
                    self.accountant.on_release(b, parked)

    def rollback_match(self, blocks) -> None:
        """Undo a :meth:`match_prefix` acquisition whose tail
        allocation failed (a blocked queue head retried every step):
        refcounts drop exactly like :meth:`release`, but the pool
        accounting is REWOUND, not observed — a rollback was never a
        residency, so no lifetime sample is recorded and a resurrected
        block re-parks under its ORIGINAL timestamp (flooding the
        lifetime histogram with ~0s samples and re-stamping LRU ages
        each retry would corrupt exactly the numbers the offload/
        eviction decision reads)."""
        self._drop_refs(blocks, rollback=True)

    # ------------------------------------------------------- prefix cache

    def match_prefix(self, hashes) -> list:
        """Walk a prompt's chain hashes in prefix order, acquiring every
        consecutive hit (refcount++ on resident blocks, resurrection out
        of the LRU for evictable ones, and — host tier armed — swap-in
        of demoted blocks through :meth:`_swap_in_hit`). Stops at the
        first miss — a deeper block is only valid under its full prefix
        chain. Returns the acquired block ids; the caller allocates the
        tail and, on tail-allocation failure, must ``release`` these
        (a rolled-back swap-in parks device-side, content intact)."""
        out = []
        for h in hashes:
            b = self._hash_to_block.get(h)
            if b is None:
                b = self._swap_in_hit(h)
                if b is None:
                    break
                out.append(b)
                continue
            if b in self._lru:
                del self._lru[b]
                self._refcount[b] = 1
                if self.accountant is not None:
                    # resurrection is a fresh residency (refcount 0->1)
                    self.accountant.on_acquire(b)
            else:
                self._refcount[b] = self._refcount[b] + 1
            out.append(b)
        return out

    def _swap_in_hit(self, h: bytes):
        """Promote one demoted (host-resident) block back to the device
        for a prefix hit: POP the payload first (the staging
        allocation below may itself demote a colder parked block, and
        on a bounded tier that demotion's capacity drop could evict
        exactly this hash — reserving the payload up front makes the
        swap-in immune to its own staging), then allocate a block off
        the free list, copy the payload in via the owner's callback,
        and re-register the hash. Returns the block id, or None when
        the hash is not host-resident (a true miss) or no block can
        stage the swap-in."""
        if (self.host_tier is None or self.on_swap_in is None
                or not self.host_tier.has(h) or self.free_blocks < 1):
            return None
        payload = self.host_tier.take(h)
        b = self._pop_free()
        self._refcount[b] = 1
        self.on_swap_in(b, payload)   # host->device into block b
        self._hash_to_block[h] = b
        self._block_hash[b] = h
        self.swap_ins += 1
        if self.accountant is not None:
            self.accountant.on_acquire(b)
        return b

    def register_prefix(self, block: int, h: bytes) -> bool:
        """Publish a live, fully-written prefix block under its chain
        hash. First writer wins: if the hash is already claimed (a
        concurrent identical prefill), this block stays private and
        recycles normally. Returns True when registered."""
        if not self.enable_prefix_caching:
            return False
        if self._refcount.get(block, 0) <= 0:
            raise ValueError(
                f"register_prefix on non-live block {block} — only a "
                "resident sequence's own blocks can be published")
        if h in self._hash_to_block or block in self._block_hash:
            return False
        self._hash_to_block[h] = block
        self._block_hash[block] = h
        if self.host_tier is not None:
            # invariant: a hash is never BOTH device-registered and
            # host-resident. A bounded tier's capacity drop can strand
            # a descendant hash on host after its chain ancestor
            # dropped; when the re-prefilled chain re-registers it
            # here, the stale host copy must go — otherwise this
            # block's next demotion reads as a double demote.
            self.host_tier.discard(h)
        return True

    def block_hash(self, block: int):
        """The chain hash a block is registered under, or None."""
        return self._block_hash.get(block)

    def lookup_prefix(self, h: bytes) -> Optional[int]:
        """The block id currently registered under a chain hash —
        resident OR parked in the evictable LRU — without touching
        refcounts or LRU order (a pure read: the disaggregation
        handoff export walks a just-retired prompt's registered
        blocks through this). None = not device-registered."""
        return self._hash_to_block.get(h)
