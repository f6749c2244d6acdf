"""Pallas decode attention over a KV cache — the inference hot path.

TPU-native analog of the reference's fused ``softmax_context`` kernel
(``csrc/transformer/inference/csrc/pt_binding.cpp:1701-1740`` /
``softmax.cu``), which attends new tokens against the accumulated KV
cache each generation step. ONE kernel body serves the whole decode
family — dense one-token decode, paged decode, paged speculative verify
and paged chunked prefill differ only in how many query tokens a slot
carries and where its causal bound starts, both of which ride as data.

The kernel streams K/V blocks through VMEM with the online-softmax
recurrence — no probability vector ever round-trips HBM. It consumes the
paged pool WHERE IT LIES: the whole stacked ``[L, NB, BS, KH*D]`` array
of kv_cache.PagedKVCache is the operand (merging the two leading dims
moves no byte), and a layer is the static block offset ``layer * NB``
the index map adds to every table entry — no program cuts a layer's K
or V out of the pool or reorders a byte of it before the call. A pool
block is DMA'd whole, as the contiguous ``[BS, KH*D]`` slab it is in
HBM, and the kv heads are walked inside the kernel as static lane
slices of that slab. (The TPU lowering only takes blocks whose last two
dims are tile-aligned or span the array, so a block cannot pick one kv
head out of ``KH`` — the per-head block of the earlier layout was
refused by the chip's compiler.) Grouped-query attention is native: each
kv head's slice is attended by its whole query group ``[rows, D]`` at
once, so GQA's bandwidth saving survives.

int8 pools (kv_cache_dtype: "int8", docs/serving.md "KV quantization &
host tiering") add per-block-per-head scale tiles ``[L, NB, KH, BS]``
(one amax/127 scale per written (position, head) row, block_size on the
LANE dim), read through the same index map. The HBM stream is the int8
bytes; the scales are applied in VMEM as ``[1, BS]`` rows against the
score / probability matrices (``(q·kᵀ)·s_k`` and ``(p·s_v)·v`` —
algebraically the dequantized product, without ever turning a scale row
into a column).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 256
# query rows (tokens x group size) one grid step keeps resident per kv
# head: bounds the q/out blocks and the online-softmax scratch in VMEM
# (~7 MiB at KH=16, D=128) however long a prefill chunk is
MAX_QUERY_ROWS = 128


def _layer_pools(k_pool, v_pool, D, k_scale, v_scale):
    """What the reference oracles attend: ONE layer's pools
    ``[NB, BS, KH*D]`` as ``[NB, BS, KH, D]``, an int8 pool dequantized
    by XLA (scales ``[NB, KH, BS]`` broadcast against it)."""
    from deepspeed_tpu.ops.quant_core import dequantize_int8
    k = k_pool.reshape(*k_pool.shape[:2], -1, D)
    v = v_pool.reshape(*v_pool.shape[:2], -1, D)
    if k_scale is None:
        return k, v
    k = dequantize_int8(k, jnp.transpose(k_scale, (0, 2, 1))[..., None])
    v = dequantize_int8(v, jnp.transpose(v_scale, (0, 2, 1))[..., None])
    return k, v


def _paged_kernel(base_ref, bt_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, head_dim: int, rep: int, span: int,
                  scale: float, quantized: bool):
    """Grid (slot, query-row block, block-table entry). The index maps
    gather whole K/V pool blocks through the scalar-prefetched block
    table, so no per-slot contiguous cache is ever materialized in HBM.
    Query rows are (token, group member) pairs, token-major, ``span``
    tokens per row block; key position ``col`` is visible to the row's
    token ``t`` iff ``col <= base[slot] + t``. Online-softmax state
    carries across the (innermost) table axis in VMEM scratch, one
    ``[rows, ·]`` plane per kv head."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    s, rb, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nb = pl.num_programs(2)
    KH, rows = q_ref.shape[1], q_ref.shape[2]
    base = base_ref[s] + rb * span     # bound of this block's first token

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks wholly beyond the last query's bound are dead for every row
    @pl.when(i * block_size <= base + span - 1)
    def _update():
        col = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        bound = base
        if span > 1:
            bound = base + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_size), 0) // rep
        visible = col <= bound
        for h in range(KH):
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, h].astype(jnp.float32) * scale    # [rows, D]
            k = k_ref[0, :, lanes].astype(jnp.float32)     # [BS, D]
            v = v_ref[0, :, lanes].astype(jnp.float32)
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            if quantized:
                sc = sc * ks_ref[0, h:h + 1, :]
            sc = jnp.where(visible, sc, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_ref[h] = m_new
            l_ref[h] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * vs_ref[0, h:h + 1, :]
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_attention(qg, k_pool, v_pool, block_tables, base, *, rep: int,
                     scale, interpret, name: str, layer: int = 0,
                     k_scale=None, v_scale=None):
    """The decode family's one ``pallas_call``, named ``name`` in the
    compiled program and the device trace (the entry point's name: the
    kernel body is shared). qg ``[S, KH, T*rep, D]``
    (each slot's T query tokens x ``rep`` group members, token-major,
    grouped by the kv head they read); pools ``[L, NB, BS, KH*D]``, the
    whole stacked pool, of which the call attends layer ``layer``
    (static); block_tables ``[S, MB]`` of that layer's block ids (dead
    entries must be valid ids — the null block); base ``[S]``: slot s's
    token t sees key positions ``<= base[s] + t``. Returns
    ``[S, KH, T*rep, D]``."""
    S, KH, rows, D = qg.shape
    L, NB, BS, W = k_pool.shape
    MB = block_tables.shape[1]
    quantized = k_scale is not None
    if (k_pool.dtype == jnp.int8) != quantized:
        raise ValueError("int8 pools require k_scale/v_scale (and fp "
                         "pools must not pass them)")
    if W != KH * D:
        raise ValueError(f"pool rows are {W} wide; {KH} kv heads x "
                         f"{D} need {KH * D}")
    if not 0 <= layer < L:
        raise ValueError(f"layer {layer} of a {L}-layer pool")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # tokens per row block: halve while the rows overrun the VMEM budget
    # and the halves still tile (a split block's sublane dim must be a
    # multiple of 8)
    span = rows // rep
    while (span * rep > MAX_QUERY_ROWS and span % 2 == 0
           and (span // 2 * rep) % 8 == 0):
        span //= 2
    rblk = span * rep

    def kv_map(s, rb, i, base, bt):
        # dead table entries re-name the slot's last live block: an
        # unchanged block index skips the DMA, so the dead tail of a
        # table costs neither bandwidth nor (pl.when above) compute
        last = jnp.maximum(base[s] + (rb + 1) * span - 1, 0) // BS
        return (bt[s, jnp.minimum(i, last)] + layer * NB, 0, 0)

    def q_map(s, rb, i, base, bt):
        return (s, 0, rb, 0)

    # the layers' blocks back to back: merging the two leading dims of
    # the stored array is free, and layer l's block b is block l*NB + b
    kv_spec = pl.BlockSpec((1, BS, W), kv_map)
    in_specs = [pl.BlockSpec((1, KH, rblk, D), q_map), kv_spec, kv_spec]
    args = [base.astype(jnp.int32), block_tables.astype(jnp.int32), qg,
            k_pool.reshape(L * NB, BS, W), v_pool.reshape(L * NB, BS, W)]
    if quantized:
        in_specs += [pl.BlockSpec((1, KH, BS), kv_map)] * 2
        args += [k_scale.reshape(L * NB, KH, BS),
                 v_scale.reshape(L * NB, KH, BS)]
    kernel = functools.partial(
        _paged_kernel, block_size=BS, head_dim=D, rep=rep, span=span,
        scale=float(scale), quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, rows // rblk, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KH, rblk, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((KH, rblk, 1), jnp.float32),
            pltpu.VMEM((KH, rblk, 1), jnp.float32),
            pltpu.VMEM((KH, rblk, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KH, rows, D), qg.dtype),
        interpret=interpret,
        name=name,
    )(*args)


def _group_size(H: int, KH: int) -> int:
    if H % KH:
        raise ValueError(f"q heads {H} not divisible by kv heads {KH}")
    return H // KH


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array,
                     block_k: int = DEFAULT_BLOCK_K,
                     scale: float | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """One-token attention against the dense cache, GQA-native.

    q: ``[B, H, D]``; k_cache/v_cache: ``[B, S, KH, D]`` (the kv_cache.py
    storage layout — no transpose) with ``H % KH == 0``; lengths: ``[B]``
    int32 live lengths (query attends positions ``< lengths[b]``).
    Returns ``[B, H, D]``. A dense cache IS a one-layer paged pool
    whose block table is the identity: row b's ``S // block_k`` blocks
    sit back to back, and the paged kernel runs as is.
    """
    B, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    R = _group_size(H, KH)
    block_k = min(block_k, S)
    if S % block_k:
        raise ValueError(f"cache size {S} not divisible by block_k {block_k}")
    nb = S // block_k
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    og = _paged_attention(
        q.reshape(B, KH, R, D),
        k_cache.reshape(1, B * nb, block_k, KH * D),
        v_cache.reshape(1, B * nb, block_k, KH * D),
        tables, lengths.astype(jnp.int32) - 1, rep=R, scale=scale,
        interpret=interpret, name="decode_attention")
    return og.reshape(B, H, D)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array,
                           scale: float | None = None,
                           interpret: bool | None = None,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           layer: int = 0) -> jax.Array:
    """One-token attention through a paged KV pool, GQA-native.

    q: ``[S, H, D]`` (one query per slot); k_pool/v_pool:
    ``[L, NB, BS, KH*D]`` (the PagedKVCache pool as stored, all layers;
    the call attends layer ``layer``, a static int);
    block_tables: ``[S, MB]`` int32 (entry j covers logical positions
    ``j*BS..(j+1)*BS-1``; dead entries must be valid ids — the null
    block); lengths: ``[S]`` int32 live lengths (the query attends
    positions ``< lengths[s]``). Returns ``[S, H, D]``.

    int8 pools pass ``k_scale``/``v_scale`` ``[L, NB, KH, BS]``; the
    grid, scratch and recurrence are unchanged (scales are two more
    streamed inputs, not a new program structure). An idle slot (length
    0) costs no compute and one null-block DMA.
    """
    S, H, D = q.shape
    KH = k_pool.shape[-1] // D
    R = _group_size(H, KH)
    og = _paged_attention(
        q.reshape(S, KH, R, D), k_pool, v_pool, block_tables,
        lengths.astype(jnp.int32) - 1, rep=R, scale=scale,
        interpret=interpret, name="paged_decode_attention", layer=layer,
        k_scale=k_scale, v_scale=v_scale)
    return og.reshape(S, H, D)


def paged_chunk_attention(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, block_table: jax.Array,
                          start: jax.Array,
                          scale: float | None = None,
                          interpret: bool | None = None,
                          k_scale: jax.Array | None = None,
                          v_scale: jax.Array | None = None,
                          layer: int = 0) -> jax.Array:
    """Chunked-prefill attention for one slot through the paged pool,
    GQA-native.

    q: ``[C, H, D]`` (the in-flight chunk, absolute positions
    ``start..start+C-1``; the chunk's own k/v must already be written
    into the pool); k_pool/v_pool: ``[L, NB, BS, KH*D]``, of which
    layer ``layer`` is attended; block_table:
    ``[MB]`` int32 (the prefilling slot's row; dead entries must be
    valid ids — the null block); start: scalar int32, block-aligned.
    The chunk attends the already-resident prefix (earlier chunks AND
    prefix-cache hits) plus itself: key position ``col`` is visible to
    chunk query ``qi`` iff ``col <= start + qi``. int8 pools pass
    ``k_scale``/``v_scale`` ``[L, NB, KH, BS]``. Returns ``[C, H, D]``.
    """
    return _paged_multi_token(
        q[None], k_pool, v_pool, block_table[None],
        jnp.reshape(start, (1,)), "paged_chunk_attention", scale=scale,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
        layer=layer)[0]


def paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array,
                           scale: float | None = None,
                           interpret: bool | None = None,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           layer: int = 0) -> jax.Array:
    """Batched speculative-verify attention through a paged KV pool,
    GQA-native.

    q: ``[S, K, H, D]`` (each slot's K-token candidate chunk at
    absolute positions ``lengths[s]..lengths[s]+K-1``; the chunk's own
    k/v must already be written into the pool —
    kv_cache.paged_write_tokens); k_pool/v_pool: ``[L, NB, BS, KH*D]``,
    of which layer ``layer`` is attended;
    block_tables: ``[S, MB]`` int32 (dead entries must be valid ids —
    the null block); lengths: ``[S]`` int32 live lengths per slot.
    Per-query causal bound ``col <= lengths[s] + qi``. Returns
    ``[S, K, H, D]``.

    ONE kernel signature per ``(K, num_slots, block geometry)`` —
    per-slot acceptance state rides in ``lengths``, so varying
    acceptance never retraces (the PR-8 trace-discipline contract).
    int8 pools pass ``k_scale``/``v_scale`` ``[L, NB, KH, BS]``."""
    return _paged_multi_token(q, k_pool, v_pool, block_tables, lengths,
                              "paged_verify_attention", scale=scale,
                              interpret=interpret, k_scale=k_scale,
                              v_scale=v_scale, layer=layer)


def _paged_multi_token(q, k_pool, v_pool, block_tables, lengths, name, *,
                       scale, interpret, k_scale, v_scale, layer):
    """What the verify and chunk entry points share: K query tokens per
    slot, the kernel call named ``name``."""
    S, K, H, D = q.shape
    KH = k_pool.shape[-1] // D
    R = _group_size(H, KH)
    # [S, K, H, D] -> [S, KH, K*R, D]: rows grouped by the kv head they
    # read, query index recoverable in-kernel as row // R
    qg = q.reshape(S, K, KH, R, D).transpose(0, 2, 1, 3, 4).reshape(
        S, KH, K * R, D)
    og = _paged_attention(qg, k_pool, v_pool, block_tables, lengths,
                          rep=R, scale=scale, interpret=interpret,
                          name=name, layer=layer, k_scale=k_scale,
                          v_scale=v_scale)
    return og.reshape(S, KH, K, R, D).transpose(0, 2, 1, 3, 4).reshape(
        S, K, H, D)


def paged_verify_attention_reference(q, k_pool, v_pool, block_tables,
                                     lengths, k_scale=None, v_scale=None):
    """Numerics oracle for :func:`paged_verify_attention` over ONE
    layer's pools ``[NB, BS, KH*D]`` (scales ``[NB, KH, BS]``): gather
    each slot's cache through its table, dense masked softmax with the
    per-query causal bound ``col <= lengths[s] + qi``. int8 pools
    dequantize up front (:func:`_layer_pools`)."""
    S, K, H, D = q.shape
    k_pool, v_pool = _layer_pools(k_pool, v_pool, D, k_scale, v_scale)
    BS, KH = k_pool.shape[1], k_pool.shape[2]
    MB = block_tables.shape[1]
    rep = H // KH
    kc = k_pool[block_tables].reshape(S, MB * BS, KH, D)
    vc = v_pool[block_tables].reshape(S, MB * BS, KH, D)
    kc = jnp.repeat(kc, rep, axis=2) if rep > 1 else kc
    vc = jnp.repeat(vc, rep, axis=2) if rep > 1 else vc
    s = jnp.einsum("skhd,sphd->shkp", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / (D ** 0.5)
    col = jnp.arange(MB * BS)[None, None, None, :]
    qi = jnp.arange(K)[None, None, :, None]
    s = jnp.where(col <= lengths[:, None, None, None] + qi, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("shkp,sphd->skhd", p,
                      vc.astype(jnp.float32)).astype(q.dtype)


def paged_chunk_attention_reference(q, k_pool, v_pool, block_table, start,
                                    k_scale=None, v_scale=None):
    """Numerics oracle for :func:`paged_chunk_attention` over ONE
    layer's pools ``[NB, BS, KH*D]``: gather the slot's cache through
    its table, dense masked softmax with the per-query causal bound
    ``col <= start + qi``. int8 pools dequantize up front."""
    C, H, D = q.shape
    k_pool, v_pool = _layer_pools(k_pool, v_pool, D, k_scale, v_scale)
    BS, KH = k_pool.shape[1], k_pool.shape[2]
    MB = block_table.shape[0]
    rep = H // KH
    kc = k_pool[block_table].reshape(MB * BS, KH, D)
    vc = v_pool[block_table].reshape(MB * BS, KH, D)
    kc = jnp.repeat(kc, rep, axis=1) if rep > 1 else kc
    vc = jnp.repeat(vc, rep, axis=1) if rep > 1 else vc
    s = jnp.einsum("chd,shd->chs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / (D ** 0.5)
    col = jnp.arange(MB * BS)[None, None, :]
    qi = jnp.arange(C)[:, None, None]
    s = jnp.where(col <= start + qi, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("chs,shd->chd", p,
                      vc.astype(jnp.float32)).astype(q.dtype)


def paged_decode_attention_reference(q, k_pool, v_pool, block_tables,
                                     lengths, k_scale=None, v_scale=None):
    """Numerics oracle over ONE layer's pools ``[NB, BS, KH*D]``: gather
    each slot's cache through its block table (gathered position j IS
    logical position j), then run the dense masked-softmax reference.
    int8 pools dequantize up front."""
    k_pool, v_pool = _layer_pools(k_pool, v_pool, q.shape[-1], k_scale,
                                  v_scale)
    S, MB = block_tables.shape
    BS = k_pool.shape[1]
    kc = k_pool[block_tables].reshape(S, MB * BS, *k_pool.shape[2:])
    vc = v_pool[block_tables].reshape(S, MB * BS, *v_pool.shape[2:])
    return decode_attention_reference(q, kc, vc, lengths)


def decode_attention_reference(q, k_cache, v_cache, lengths):
    """Numerics oracle (pure jnp, XLA) — also the CPU fallback path.
    Same layouts as :func:`decode_attention`."""
    B, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    rep = H // KH
    kc = jnp.repeat(k_cache, rep, axis=2) if rep > 1 else k_cache
    vc = jnp.repeat(v_cache, rep, axis=2) if rep > 1 else v_cache
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / (D ** 0.5)
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p,
                      vc.astype(jnp.float32)).astype(q.dtype)
