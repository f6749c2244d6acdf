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
of kv_cache.PagedKVCache is the operand, left in HBM (merging the two
leading dims moves no byte), and a layer is the static block offset
``layer * NB`` added to every table entry — no program cuts a layer's K
or V out of the pool or reorders a byte of it before the call.

The walk (``block_walk.walk_live_blocks``: the scaffold, shared with
the latent pool's decode kernel, is described there). The grid is
``(slot, query-row block)`` and runs in order; one grid step walks its
slot's LIVE blocks in a loop whose trip count, ``ceil(positions seen /
BS)``, is read from the scalar-prefetched bounds. Block ``j`` of the
table is copied whole, as the contiguous ``[BS, KH*D]`` slab it is in
HBM, into one of two VMEM buffers while block ``j - 1`` is attended, and
the last block's iteration starts the first block of the next grid step
that has one. A dead table entry is never read; an idle slot (bound
below zero) costs one empty grid step that writes zeros.

Several entries an iteration. On the chip a block costs the larger of
its bytes' time and the latency of one iteration's chain (wait for the
copy, score product, two lane reductions, ``exp``, ``p . v``, the
accumulator's rescale: 0.75-0.9 us, where a MiMo-V2 full layer's 327 KB
block is 0.40 us of bytes), and the loop runs one chain after another.
So the one-token walk over a full-precision pool covers ``G`` table
entries an iteration (:func:`_entries_per_iteration`: about a mebibyte
of K + V, at most two where each head has a product of its own, from
the static shapes alone), entry ``j*G + k`` through K and
V streams ``k`` of its own (``2 G`` streams, two buffers each; an entry
past the slot's last live block sits out: no copy, no arithmetic): all
``G`` score products first, which depend on nothing but their blocks,
then the online-softmax recurrence over them in table order, carried in
values and stored once. The float32 sums and their order are the
one-entry walk's, so the outputs are its outputs to the bit; a call
whose blocks are a mebibyte already (16 heads of 128), a verify window,
a prefill chunk and an int8 pool walk one entry an iteration, and their
traced kernel is the one it was.

The arithmetic. K and V go to the MXU as they are stored (bfloat16
pools: no float32 copy of a block; int8 blocks are cast to the query's
dtype, which holds them exactly), the softmax scale is applied to the
float32 scores, and the running max, sum and accumulator are float32.
``p`` goes into ``p·v`` in the query's dtype; a 16-bit dtype takes it
in two parts (what the cast keeps and what it drops, a product each
into one float32 sum), so the probabilities keep float32's worth of
mantissa: on the chip the second product costs 0.7 % of a call, and
the served tokens are the float32-operand kernel's to the digit. How the kv heads of a block share the MXU is chosen from the
static shapes alone:

- few query rows in all (``KH * rows <= MAX_BATCHED_ROWS``: one-token
  decode, small verify windows): the queries form a block-diagonal
  ``[KH*rows, KH*D]`` operand (built once a slot in VMEM), so ONE
  product against the slab gives all heads' scores ``[KH*rows, BS]``
  with the heads down the sublanes — one max / exp / sum over full
  registers — and ``p·v`` ``[KH*rows, KH*D]`` holds every head's output
  in its diagonal ``D``-wide lane block, picked out once at the end.
  Each K and V tile passes through the MXU once, as it would anyway;
  what goes is a product, a cast and a one-sublane softmax per head.
  With ``G`` entries an iteration the ``G`` products of an iteration
  are issued together, ahead of the recurrence that consumes them.
- more rows (prefill chunks, wide verify windows): a product per kv
  head at M = rows against that head's static lane slice of the slab
  (the TPU lowering only takes blocks whose last two dims are
  tile-aligned or span the array, so a copy cannot pick one kv head out
  of ``KH``). With ``G`` entries an iteration a head's ``G`` products
  come first and its recurrence after them, head by head. Grouped-query
  attention is native on both paths: a kv head's slice is attended by
  its whole query group at once, so GQA's bandwidth saving survives.
- key heads whose lanes do not tile (:func:`_ragged`: 192, where every
  other head of a row starts inside a 128-lane tile) have no aligned
  slice for a product of their own: they share the block-diagonal
  product up to ``MAX_RAGGED_ROWS`` query rows (MiMo-V2's decode: 4
  heads x 16 rows and 8 x 8, operands ``[64, 768]`` / ``[64, 1536]``,
  still under the time the block's bytes take), and more rows are
  refused by name.

A value head may be narrower (or wider) than a key head: V's rows are
``KH * Dv`` wide beside K's ``KH * D``, the accumulator and the output
``Dv`` wide a head; nothing else changes. A ``sink [H]`` (float32, one
query token a slot) is a learned logit a query head that joins the
softmax's denominator and carries no value: the online softmax's carry
starts at ``(m, l, acc) = (sink, 1, 0)`` in place of ``(-inf, 0, 0)``.
With ``Dv == D`` and no sink the traced kernel is the one it was.

int8 pools (kv_cache_dtype: "int8", docs/serving.md "KV quantization &
host tiering") add per-block-per-head scale tiles ``[L, NB, KH, BS]``
(one amax/127 scale per written (position, head) row, block_size on the
LANE dim) that ride the same walk: two more copies a block into two
more pairs of buffers. The HBM stream is the int8 bytes; the scales are
applied in VMEM as rows against the score / probability matrices
(``(q·kᵀ)·s_k`` and ``(p·s_v)·v`` — algebraically the dequantized
product, without ever turning a scale row into a column).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.block_walk import live_blocks, walk_live_blocks
from deepspeed_tpu.telemetry.registry import get_registry

NEG_INF = -1e30
DEFAULT_BLOCK_K = 256
# query rows (tokens x group size) one grid step keeps resident per kv
# head: bounds the q/out blocks and the online-softmax scratch in VMEM
# (~7 MiB at KH=16, D=128) however long a prefill chunk is
MAX_QUERY_ROWS = 128
# query rows of ALL kv heads that one product may carry: up to here the
# heads of a block share a block-diagonal product, beyond it each head
# has rows enough for a product of its own (the shared accumulator grows
# with the square of the head count)
MAX_BATCHED_ROWS = 32
# ... and what it may carry when a key head's lanes do not tile (a head
# of 192 starts every other head in the middle of a 128-lane tile, so a
# product per head has no aligned slice of the slab to take): such heads
# share the block-diagonal product up to here, and more rows are refused
MAX_RAGGED_ROWS = 64


def _layer_pools(k_pool, v_pool, D, k_scale, v_scale):
    """What the reference oracles attend: ONE layer's pools
    ``[NB, BS, KH*D]`` / ``[NB, BS, KH*Dv]`` as ``[NB, BS, KH, D]`` /
    ``[NB, BS, KH, Dv]`` (``D`` the query's width; a value's follows from
    the head count), an int8 pool dequantized by XLA (scales ``[NB, KH,
    BS]`` broadcast against it)."""
    from deepspeed_tpu.ops.quant_core import dequantize_int8
    k = k_pool.reshape(*k_pool.shape[:2], -1, D)
    v = v_pool.reshape(*v_pool.shape[:2], k.shape[2], -1)
    if k_scale is None:
        return k, v
    k = dequantize_int8(k, jnp.transpose(k_scale, (0, 2, 1))[..., None])
    v = dequantize_int8(v, jnp.transpose(v_scale, (0, 2, 1))[..., None])
    return k, v


def _paged_kernel(base_ref, bt_ref, offset_ref, q_ref, *rest,
                  block_size: int, head_dim: int, rep: int, span: int,
                  scale: float, quantized: bool, batched: bool,
                  window: int = 0, v_head_dim: int = 0, sink: bool = False,
                  entries: int = 1):
    """Grid (slot, query-row block): one step walks ITS slot's live
    blocks (:func:`~deepspeed_tpu.ops.pallas.block_walk.walk_live_blocks`
    has the scaffold: two VMEM buffers a stream, the next step's first
    block started in this step's last iteration, the buffer parity in
    SMEM). The pools stay in HBM; table entry ``j`` of a slot is block
    ``bt[slot, j] + offset[0]`` (a layer is ``layer * NB`` blocks in).
    The trip count ``ceil(positions seen / BS)`` is read from the
    scalar-prefetched ``base``: a dead table entry costs nothing, an
    idle slot one grid step that writes zeros.

    Query rows are (token, group member) pairs, token-major, ``span``
    tokens per row block; key position ``col`` is visible to the row's
    token ``t`` iff ``col <= base[slot] + t``. K and V enter the MXU as
    stored (an int8 block cast to the query's dtype, exactly), p in two
    parts when that dtype has 16 bits; the scores, ``m``, ``l`` and the
    accumulator are float32. How the heads
    share a block's products is static (``batched``):

    - few rows a head (decode): q is the block-diagonal ``[KH*rows,
      KH*D]`` operand, so ONE product against the slab ``[BS, KH*D]``
      gives every head's scores with heads down the sublanes
      (``[KH*rows, BS]``: one max / exp / sum over full registers), and
      ``p @ v`` gives every head's output in the diagonal ``D``-wide
      lane blocks of one ``[KH*rows, KH*D]`` accumulator, picked out at
      the end;
    - else (verify, prefill chunks) a product per kv head at M = rows
      against that head's lane slice of the slab, one ``[rows, ·]``
      scratch plane a head.

    ``window`` (static; 0: none, and the body above is all there is): the
    table is a slot's RING of ``MB`` blocks (kv_cache.PagedKVCache
    ``ring_k``): row ``r`` of it holds the newest position ``p <= base``
    with ``p = r (mod MB*BS)``, and the one query token sees it iff ``0
    <= p`` and ``base - p < window``. The walk is the same walk: a ring
    has ``min(ceil((base + 1) / BS), MB)`` live blocks, so a context
    however long reads at most the ring.

    ``v_head_dim`` (static; 0: the keys' ``head_dim``): a value head's
    width where it is not a key head's: the V slab is ``[BS, KH*Dv]`` and
    the output and the accumulator ``Dv`` wide a head. ``sink`` (static):
    one more operand, a float32 logit a query row (``sink_ref``, shaped
    as ``m``), that joins the softmax's denominator and carries no value:
    the carry starts at ``(m, l, acc) = (sink, 1, 0)`` in place of
    ``(-inf, 0, 0)``. An idle slot still writes zeros.

    ``entries`` (static, ``G``; 1: a table entry an iteration, and the
    body above is all there is): an iteration of the walk covers table
    entries ``j*G .. j*G + G - 1`` of its slot, each through K and V
    streams of its own (``2 G`` streams; an entry past the slot's last
    live block sits out: no copy, no arithmetic), so the trip count is
    ``ceil(live blocks / G)``. One query token a slot over a
    full-precision pool only (:func:`_entries_per_iteration`).
    """
    G = entries
    sink_ref = None
    if sink:
        sink_ref, *rest = rest
    # K, V and an int8 pool's two scale-tile arrays; a pair of VMEM
    # buffers each, G times over
    P = 4 if quantized else 2
    pools, (o_ref, *rest) = rest[:P], rest[P:]
    bufs, (sems, next_buf, m_ref, l_ref, acc_ref, *rest) = (
        rest[:P * G], rest[P * G:])
    streams = tuple((pools[i % P], buf) for i, buf in enumerate(bufs))
    k_bufs, v_bufs = bufs[0::P], bufs[1::P]
    k_buf, v_buf = k_bufs[0], v_bufs[0]
    ks_buf, vs_buf = bufs[2:] if quantized else (None, None)
    qbd_ref, = rest or (None,)      # the block-diagonal q, if batched
    s, rb = pl.program_id(0), pl.program_id(1)
    S, RB = pl.num_programs(0), pl.num_programs(1)
    MB = bt_ref.shape[1]
    D, BS = head_dim, block_size
    Dv = v_head_dim or D
    KH = k_buf.shape[-1] // D
    rows = acc_ref.shape[-2] // KH if batched else acc_ref.shape[-2]
    cdt = q_ref.dtype

    base = base_ref[s] + rb * span     # bound of this block's first token
    blocks = live_blocks(base + span, BS, MB)

    def trips(blocks):
        """Loop iterations that walk ``blocks`` table entries."""
        return blocks if G == 1 else jax.lax.div(blocks + (G - 1), G)
    # the grid step after this one, and whether it has a block to fetch
    wraps = rb + 1 == RB
    s_next = jnp.minimum(jnp.where(wraps, s + 1, s), S - 1)
    rb_next = jnp.where(wraps, 0, rb + 1)
    n_next = jnp.where(
        jnp.logical_and(wraps, s + 1 == S), 0,
        trips(live_blocks(base_ref[s_next] + (rb_next + 1) * span, BS, MB)))

    def block_ids(slot, j):
        """The blocks of group ``j`` of ``slot``, one a stream. ``G > 1``
        walks one query token a slot, so a slot's live blocks are the
        slot's alone; the group's first entry is live whenever the walk
        reaches the group."""
        if G == 1:
            return bt_ref[slot, j] + offset_ref[0]
        live = live_blocks(base_ref[slot] + span, BS, MB)
        ids = [bt_ref[slot, j * G] + offset_ref[0]] + [
            (bt_ref[slot, jnp.minimum(j * G + k, MB - 1)] + offset_ref[0],
             j * G + k < live) for k in range(1, G)]
        return tuple(block for block in ids for _ in range(P))

    def idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    def walk(loop):
        if sink:
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        R = acc_ref.shape[-2]
        row = jax.lax.broadcasted_iota(jnp.int32, (R, BS), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (R, BS), 1)
        # a row's bound; a block compares it less its own first column
        bound = base
        if span > 1:
            bound = base + (row % rows if batched else row) // rep

        def own_head(pick, width, zero=0.0):
            """Batched rows: ``pick(h)`` (``[R or 1, width]``) where row
            r belongs to head h, zero elsewhere, for every h."""
            head = jax.lax.broadcasted_iota(jnp.int32, (R, width), 0) // rows
            return [jnp.where(head == h, pick(h), zero) for h in range(KH)]

        if batched:     # q on the diagonal, one lane block a head
            zero = jnp.zeros((), cdt)
            for h, q in enumerate(own_head(lambda h: q_ref[0], D, zero)):
                qbd_ref[:, h * D:(h + 1) * D] = q

        def rows_of(tile):
            """``[KH, BS]`` scale tile -> a scale row per query row."""
            if rows == 1:
                return tile
            return functools.reduce(
                jnp.add, own_head(lambda h: tile[h:h + 1, :], BS))

        def softmax_step(sc, m_prev, l_prev, visible):
            sc = jnp.where(visible, sc, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            return p, alpha, m_new, l_prev * alpha + jnp.sum(
                p, axis=-1, keepdims=True)

        def operand(x):
            return x if x.dtype == cdt else x.astype(cdt)

        def p_dot_v(p, v):
            """``p @ v`` with p at float32's worth of mantissa: in a
            16-bit dtype as two parts (what the cast keeps, and what it
            drops), a product each into one float32 sum."""
            def dot(part):
                return jax.lax.dot_general(
                    part, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            kept = p.astype(cdt)
            if cdt == jnp.float32:
                return dot(kept)
            return dot(kept) + dot((p - kept.astype(jnp.float32)).astype(cdt))

        if window:
            # how many positions ago a ring row was written: the row of
            # position ``base`` 0, the one before it 1, ... wrapping
            newest = jax.lax.rem(base, MB * BS)

        def visible_in(j):
            """Which columns of table entry ``j`` each query row sees."""
            if window:
                age = newest - j * BS - col
                age = jnp.where(age < 0, age + MB * BS, age)
                return jnp.logical_and(age < window, age <= base)
            return col <= bound - j * BS

        def score(q, k):
            return jax.lax.dot_general(
                q, operand(k), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        def attend(j, buf):
            visible = visible_in(j)
            if batched:
                sc = score(qbd_ref[...], k_buf[buf])
                if quantized:
                    sc = sc * rows_of(ks_buf[buf])
                p, alpha, m_ref[...], l_ref[...] = softmax_step(
                    sc, m_ref[...], l_ref[...], visible)
                if quantized:
                    p = p * rows_of(vs_buf[buf])
                acc_ref[...] = acc_ref[...] * alpha + p_dot_v(
                    p, operand(v_buf[buf]))
                return
            for h in range(KH):
                lanes = slice(h * D, (h + 1) * D)
                v_lanes = slice(h * Dv, (h + 1) * Dv)
                sc = score(q_ref[0, h], k_buf[buf, :, lanes])
                if quantized:
                    sc = sc * ks_buf[buf, h:h + 1, :]
                p, alpha, m_ref[h], l_ref[h] = softmax_step(
                    sc, m_ref[h], l_ref[h], visible)
                if quantized:
                    p = p * vs_buf[buf, h:h + 1, :]
                acc_ref[h] = acc_ref[h] * alpha + p_dot_v(
                    p, operand(v_buf[buf, :, v_lanes]))

        def attend_entries(j, buf, live: int):
            """The first ``live`` entries of group ``j``, entry ``k`` in
            buffer ``buf`` of streams ``k``: every entry's score product
            first (they wait for nothing but their block), then the
            recurrence over them in table order, carried in values and
            stored once. The sums and their order are :func:`attend`'s:
            the outputs are a walk's of one entry an iteration, to the
            bit."""
            if batched:     # one product over all heads, else one a head
                heads = [(Ellipsis, qbd_ref[...], slice(None), slice(None))]
            else:
                heads = [(h, q_ref[0, h], slice(h * D, (h + 1) * D),
                          slice(h * Dv, (h + 1) * Dv)) for h in range(KH)]
            visible = [visible_in(j * G + k) for k in range(live)]
            for i, q, lanes, v_lanes in heads:
                scores = [score(q, k_bufs[k][buf, :, lanes])
                          for k in range(live)]
                m, l, acc = m_ref[i], l_ref[i], acc_ref[i]
                for k, sc in enumerate(scores):
                    p, alpha, m, l = softmax_step(sc, m, l, visible[k])
                    acc = acc * alpha + p_dot_v(
                        p, operand(v_bufs[k][buf, :, v_lanes]))
                m_ref[i], l_ref[i], acc_ref[i] = m, l, acc

        def attend_group(j, buf):
            # only a walk's last group can be short of entries: a branch
            # a live count, one taken
            live = jnp.minimum(blocks - j * G, G)
            for k in range(1, G + 1):
                pl.when(live == k)(
                    functools.partial(attend_entries, j, buf, k))

        loop(attend if G == 1 else attend_group)
        l = jnp.maximum(l_ref[...], 1e-30)
        if batched:     # each row's own diagonal block of the accumulator
            out = functools.reduce(jnp.add, own_head(
                lambda h: acc_ref[:, h * Dv:(h + 1) * Dv], Dv))
            o_ref[0] = (out / l).astype(o_ref.dtype)
        else:
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    walk_live_blocks(
        streams, sems, next_buf, block_ids,
        slot=s, n=trips(blocks), first=jnp.logical_and(s == 0, rb == 0),
        slot_next=s_next, n_next=n_next, idle=idle, walk=walk)


def _paged_attention(qg, k_pool, v_pool, block_tables, base, *, rep: int,
                     scale, interpret, name: str, layer: int = 0,
                     k_scale=None, v_scale=None, window: int = 0,
                     sink=None, entries: int | None = None):
    """The decode family's one ``pallas_call``, named ``name`` in the
    compiled program and the device trace (the entry point's name: the
    kernel body is shared). qg ``[S, KH, T*rep, D]``
    (each slot's T query tokens x ``rep`` group members, token-major,
    grouped by the kv head they read); pools ``[L, NB, BS, KH*D]``, the
    whole stacked pool, of which the call attends layer ``layer``
    (static), V's rows ``KH*Dv`` wide where a value is not as wide as a
    key; block_tables ``[S, MB]`` of that layer's block ids (only
    the entries a slot's bound reaches are read); base ``[S]``: slot s's
    token t sees key positions ``<= base[s] + t``; ``sink [KH*rep]``
    float32 (one query token a slot only): a logit a query head that
    joins the softmax's denominator and carries no value. Returns
    ``[S, KH, T*rep, Dv]``. ``entries`` pins the table entries a loop
    iteration attends, for a test or a measurement (None: what
    :func:`_entries_per_iteration` gives the call's shapes).

    The layer reaches the kernel as DATA (its block offset, one more
    prefetched scalar), so the calls of a model's layers are one traced
    kernel reused (:func:`_paged_call`), not a trace a layer."""
    S, KH, rows, D = qg.shape
    L, NB, BS, W = k_pool.shape
    Wv = v_pool.shape[-1]
    quantized = k_scale is not None
    if (k_pool.dtype == jnp.int8) != quantized:
        raise ValueError("int8 pools require k_scale/v_scale (and fp "
                         "pools must not pass them)")
    if W != KH * D or Wv % KH or v_pool.shape[:3] != (L, NB, BS):
        raise ValueError(f"pool rows are {W} (K) and {Wv} (V) wide; {KH} "
                         f"kv heads x {D} need {KH * D} of K and a "
                         f"multiple of {KH} of V")
    if sink is not None and (rows != rep or sink.shape != (KH * rep,)):
        raise ValueError(
            f"a sink of shape {sink.shape} for {KH * rep} query heads and "
            f"{rows // rep} query tokens a slot: one float32 a query head, "
            "one query token a slot")
    if _ragged(D) and KH * rows > MAX_RAGGED_ROWS:
        raise ValueError(
            f"key heads of {D} lanes do not tile the 128 lanes, so the "
            f"heads of a block share one product, which carries at most "
            f"{MAX_RAGGED_ROWS} query rows; {KH} kv heads x {rows} rows "
            f"are {KH * rows}")
    if not 0 <= layer < L:
        raise ValueError(f"layer {layer} of a {L}-layer pool")
    if window and (rows != rep or quantized
                   or window > block_tables.shape[1] * BS):
        raise ValueError(
            f"a window of {window} over a ring of {block_tables.shape[1]} "
            f"blocks of {BS}: one query token a slot, a full-precision "
            "ring at least as long as the window")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # the layers' blocks back to back: merging the two leading dims of
    # the stored array is free, and layer l's block b is block l*NB + b
    pools = [k_pool.reshape(L * NB, BS, W), v_pool.reshape(L * NB, BS, Wv)]
    if quantized:
        pools += [k_scale.reshape(L * NB, KH, BS),
                  v_scale.reshape(L * NB, KH, BS)]
    call = _paged_call(
        name, bool(interpret), (S, KH, rows, D), qg.dtype.name,
        tuple((x.shape, x.dtype.name) for x in pools),
        block_tables.shape[1], rep, float(scale), int(window),
        sink is not None, entries)
    sinks = () if sink is None else (sink.astype(jnp.float32),)
    return call(base.astype(jnp.int32), block_tables.astype(jnp.int32),
                jnp.full((1,), layer * NB, jnp.int32), qg, *sinks, *pools)


def _ragged(D: int) -> bool:
    """A head of ``D`` lanes neither fills whole 128-lane tiles nor
    divides one: every other head of a row starts inside a tile."""
    return bool(D % 128 and 128 % D)


# K + V bytes a loop iteration of the one-token walk should carry, and
# the most table entries it may take to carry them: where one product
# carries all heads of a block, and where each head has a product of its
# own. The kernel alone on a v5e (PERF.md section 7, PR 57; us a call at
# 1 / 2 / 3 / 4 entries an iteration, outputs equal to the bit). One
# product: MiMo-V2 full layers (blocks of 328 KB) 1605 / 1188 / 1054 /
# 1029; its rings of two blocks (655 KB) 268 / 225; Granite (524 KB)
# 1202 / 1017 / 1019 / 1021. A product a head gains at two entries and
# loses past them: Laguna full layers (524 KB, 8 heads) 1782 / 1487 /
# 1955 / 1818, its rings of five 454 / 427 / 526 / 605, 4 heads x 16
# rows over 262 KB blocks (no cell's) 1392 / 984 / 1197 / 1080. GPT-2
# 1.3B (1049 KB) 202 at every count. A mebibyte, rounded to whole
# blocks, takes the best or within 2.3 % of it in each
ITERATION_BYTES = 1 << 20
MAX_ENTRIES = 4
MAX_ENTRIES_A_HEAD = 2


def _entries_per_iteration(slab_bytes: int, MB: int, one_token: bool,
                           quantized: bool, batched: bool) -> int:
    """Table entries a loop iteration of :func:`_paged_kernel` attends
    (its ``G``), from what a call can see: ``slab_bytes`` of K + V a
    table entry, tables of ``MB`` entries, whether one product carries
    all heads of a block (``batched``). A block's cost on the chip is
    the larger of its bytes' time and the latency of one iteration's
    chain (wait for the copy, score product, two lane reductions,
    ``exp``, ``p . v`` in two parts, the accumulator's rescale: 0.75-0.9
    us), so small blocks walk several an iteration, about
    ``ITERATION_BYTES`` in all, and a block that size or larger one.
    Only the one-token full-precision signature takes more than one:
    verify windows and prefill chunks do a block's worth of work a
    block, and int8 pools run in no measured cell."""
    if not one_token or quantized:
        return 1
    most = MAX_ENTRIES if batched else MAX_ENTRIES_A_HEAD
    return max(1, min((ITERATION_BYTES + slab_bytes // 2) // slab_bytes,
                      MB, most))


@functools.lru_cache(maxsize=None)
def _paged_call(name: str, interpret: bool, q_shape, q_dtype: str, pools,
                MB: int, rep: int, scale: float, window: int = 0,
                sink: bool = False, entries: int | None = None):
    """The ``pallas_call`` of one static signature: ``(base [S], tables
    [S, MB], block offset [1], qg [S, KH, rows, D][, sink [KH*rows]],
    *pools) -> [S, KH, rows, Dv]``. Grid ``(S, row blocks)``, in order; the pools (``pools``:
    shape and dtype of K, V and an int8 pool's two scale-tile arrays,
    layers merged into the block dim) stay in HBM and
    :func:`_paged_kernel` copies the blocks it walks into two VMEM
    buffers a stream. The heads share one product a block when all
    their rows fit ``MAX_BATCHED_ROWS`` (``MAX_RAGGED_ROWS`` where a
    key head's lanes do not tile: :func:`_ragged`), and an iteration of
    the walk attends :func:`_entries_per_iteration` table entries
    (``entries`` pins them), which the gauge
    ``paged_decode_entries_per_iteration{kernel, slab_bytes}`` says of
    every signature built. Kept per signature, because
    jax traces a call it has seen before from its cache: the 24 layers
    of a decode program trace the kernel body once."""
    S, KH, rows, D = q_shape
    (_, BS, W), pool_dtype = pools[0]
    Wv = pools[1][0][2]
    Dv = Wv // KH
    batched = KH * rows <= (MAX_RAGGED_ROWS if _ragged(D)
                            else MAX_BATCHED_ROWS)
    slab = BS * (W + Wv) * jnp.dtype(pool_dtype).itemsize
    G = entries or _entries_per_iteration(slab, MB, rows == rep,
                                          len(pools) == 4, batched)
    if G > 1 and (rows != rep or len(pools) == 4 or G > MB):
        raise ValueError(
            f"{G} table entries an iteration: one query token a slot, a "
            f"full-precision pool, tables of at least {G} entries")
    get_registry().gauge(
        "paged_decode_entries_per_iteration",
        "table entries a loop iteration of the paged decode kernel "
        "attends, by kernel name and K + V bytes a table entry",
        labels={"kernel": name, "slab_bytes": str(slab)}).set(G)
    # tokens per row block: halve while the rows overrun the VMEM budget
    # and the halves still tile (a split block's sublane dim must be a
    # multiple of 8)
    span = rows // rep
    while (span * rep > MAX_QUERY_ROWS and span % 2 == 0
           and (span // 2 * rep) % 8 == 0):
        span //= 2
    rblk = span * rep
    f32 = jnp.float32
    if batched:
        # heads down the rows: merging the two dims moves no byte
        q_block, o_block = (S, KH * rows, D), (S, KH * rows, Dv)
        m_shape = (KH * rows, 1)
        spec = lambda d: pl.BlockSpec(                      # noqa: E731
            (1, KH * rows, d), lambda s, rb, *_: (s, 0, 0))
        softmax_state = [pltpu.VMEM(m_shape, f32)] * 2 + [
            pltpu.VMEM((KH * rows, Wv), f32),
            pltpu.VMEM((KH * rows, W), q_dtype)]    # block-diagonal q
    else:
        q_block, o_block = q_shape, (S, KH, rows, Dv)
        m_shape = (KH, rblk, 1)
        spec = lambda d: pl.BlockSpec(                      # noqa: E731
            (1, KH, rblk, d), lambda s, rb, *_: (s, 0, rb, 0))
        softmax_state = [pltpu.VMEM(m_shape, f32)] * 2 + [
            pltpu.VMEM((KH, rblk, Dv), f32)]
    # the sink: one block shaped as the running max, the same every step
    sink_spec = [pl.BlockSpec(m_shape, lambda s, rb, *_: (0,) * len(m_shape))
                 ] if sink else []
    kernel = functools.partial(
        _paged_kernel, block_size=BS, head_dim=D, rep=rep, span=span,
        scale=scale, quantized=len(pools) == 4, batched=batched,
        window=window, v_head_dim=Dv, sink=sink, entries=G)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, rows // rblk),
            in_specs=[spec(D)] + sink_spec + [
                pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=spec(Dv),
            scratch_shapes=[
                *[pltpu.VMEM((2, *shape[1:]), dtype)
                  for shape, dtype in pools] * G,
                pltpu.SemaphoreType.DMA((len(pools) * G, 2)),
                pltpu.SMEM((1,), jnp.int32), *softmax_state]),
        out_shape=jax.ShapeDtypeStruct(o_block, q_dtype),
        # in order: a step starts the next step's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )
    def run(base, tables, offset, qg, *rest):
        if sink:
            rest = (rest[0].reshape(m_shape), *rest[1:])
        return call(base, tables, offset, qg.reshape(q_block),
                    *rest).reshape(*q_shape[:3], Dv)
    return run


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
                           layer: int = 0,
                           sink: jax.Array | None = None) -> jax.Array:
    """One-token attention through a paged KV pool, GQA-native.

    q: ``[S, H, D]`` (one query per slot); k_pool ``[L, NB, BS, KH*D]``
    and v_pool ``[L, NB, BS, KH*Dv]`` (the PagedKVCache pool as stored,
    all layers; the call attends layer ``layer``, a static int; a value
    may be narrower or wider than a key);
    block_tables: ``[S, MB]`` int32 (entry j covers logical positions
    ``j*BS..(j+1)*BS-1``; entries beyond a slot's length are never
    read); lengths: ``[S]`` int32 live lengths (the query attends
    positions ``< lengths[s]``); sink: ``[H]`` float32 or None, a
    learned logit a query head that joins the softmax's denominator and
    carries no value. Returns ``[S, H, Dv]``.

    int8 pools pass ``k_scale``/``v_scale`` ``[L, NB, KH, BS]``; the
    grid, the walk and the recurrence are unchanged (scales are two
    more streams of the same block walk, not a new program structure).
    An idle slot (length 0) reads nothing and returns zeros.
    """
    S, H, D = q.shape
    KH = k_pool.shape[-1] // D
    R = _group_size(H, KH)
    og = _paged_attention(
        q.reshape(S, KH, R, D), k_pool, v_pool, block_tables,
        lengths.astype(jnp.int32) - 1, rep=R, scale=scale,
        interpret=interpret, name="paged_decode_attention", layer=layer,
        k_scale=k_scale, v_scale=v_scale, sink=sink)
    return og.reshape(S, H, -1)


def ring_tables(num_slots: int, ring_blocks: int) -> jax.Array:
    """``[S, RB]``: slot ``s``'s ring is blocks ``s*RB .. s*RB + RB - 1``
    of a window layer's buffer (kv_cache.PagedKVCache ``ring_k``)."""
    return (jnp.arange(num_slots, dtype=jnp.int32)[:, None] * ring_blocks
            + jnp.arange(ring_blocks, dtype=jnp.int32)[None])


def paged_window_decode_attention(q: jax.Array, k_ring: jax.Array,
                                  v_ring: jax.Array, lengths: jax.Array,
                                  window: int,
                                  scale: float | None = None,
                                  interpret: bool | None = None,
                                  layer: int = 0,
                                  sink: jax.Array | None = None
                                  ) -> jax.Array:
    """One-token attention of a WINDOW layer through its rings,
    GQA-native: the paged kernel's walk over a table computed from the
    slot, rows masked by the position they hold.

    q: ``[S, H, D]``; k_ring ``[Lw, S*RB, BS, KH*D]`` and v_ring ``[Lw,
    S*RB, BS, KH*Dv]`` (all window layers' rings as stored; the call
    attends ring layer ``layer``, a static int); sink ``[H]`` float32 or
    None, as :func:`paged_decode_attention`'s; lengths: ``[S]`` int32 live lengths (the query sits at
    position ``lengths[s] - 1`` and sees positions ``> lengths[s] - 1 -
    window``). A slot reads ``min(ceil(lengths / BS), RB)`` blocks
    whatever its context; an idle slot (length 0) reads nothing and
    returns zeros. Returns ``[S, H, Dv]``."""
    S, H, D = q.shape
    KH = k_ring.shape[-1] // D
    R = _group_size(H, KH)
    og = _paged_attention(
        q.reshape(S, KH, R, D), k_ring, v_ring,
        ring_tables(S, k_ring.shape[1] // S),
        lengths.astype(jnp.int32) - 1, rep=R, scale=scale,
        interpret=interpret, name="paged_window_decode_attention",
        layer=layer, window=window, sink=sink)
    return og.reshape(S, H, -1)


def _sink_softmax(s, seen, sink):
    """Softmax of ``s [B, H, n]`` over the ``seen`` columns; with a
    ``sink [H]`` one more logit column a head, dropped after it took its
    share of the probability."""
    s = jnp.where(seen, s, NEG_INF)
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    col = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None],
                           (*s.shape[:2], 1))
    return jax.nn.softmax(jnp.concatenate([s, col], -1), axis=-1)[..., :-1]


def paged_window_decode_attention_reference(q, k_ring, v_ring, lengths,
                                            window: int, sink=None):
    """Numerics oracle over ONE window layer's rings ``[S*RB, BS,
    KH*D]`` / ``[S*RB, BS, KH*Dv]`` (and the path off the TPU): every
    ring row with the position it holds, a dense softmax over the rows
    inside the window (and the ``sink [H]`` column, if any)."""
    from deepspeed_tpu.inference.kv_cache import ring_newest_position
    S, H, D = q.shape
    k = k_ring.reshape(S, -1, k_ring.shape[-1] // D, D)     # [S, R, KH, D]
    v = v_ring.reshape(S, k.shape[1], k.shape[2], -1)       # [S, R, KH, Dv]
    rep = H // k.shape[2]
    newest = lengths.astype(jnp.int32) - 1
    pos = ring_newest_position(newest, k.shape[1])          # [S, R]
    seen = (pos >= 0) & (pos > newest[:, None] - window)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   jnp.repeat(k, rep, axis=2).astype(jnp.float32)
                   ) / (D ** 0.5)
    p = jnp.where(seen[:, None, :],
                  _sink_softmax(s, seen[:, None, :], sink), 0.0)
    return jnp.einsum("bhs,bshd->bhd", p,
                      jnp.repeat(v, rep, axis=2).astype(jnp.float32)
                      ).astype(q.dtype)


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
    ``[MB]`` int32 (the prefilling slot's row; entries beyond the chunk's
    end are never read); start: scalar int32, block-aligned.
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
    block_tables: ``[S, MB]`` int32 (entries beyond a slot's window are
    never read); lengths: ``[S]`` int32 live lengths per slot.
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
                                     lengths, k_scale=None, v_scale=None,
                                     sink=None):
    """Numerics oracle over ONE layer's pools ``[NB, BS, KH*D]`` /
    ``[NB, BS, KH*Dv]``: gather each slot's cache through its block
    table (gathered position j IS logical position j), then run the
    dense masked-softmax reference. int8 pools dequantize up front."""
    k_pool, v_pool = _layer_pools(k_pool, v_pool, q.shape[-1], k_scale,
                                  v_scale)
    S, MB = block_tables.shape
    BS = k_pool.shape[1]
    kc = k_pool[block_tables].reshape(S, MB * BS, *k_pool.shape[2:])
    vc = v_pool[block_tables].reshape(S, MB * BS, *v_pool.shape[2:])
    return decode_attention_reference(q, kc, vc, lengths, sink)


def decode_attention_reference(q, k_cache, v_cache, lengths, sink=None):
    """Numerics oracle (pure jnp, XLA) — also the CPU fallback path.
    Same layouts as :func:`decode_attention` (``v_cache`` may be ``[B, S,
    KH, Dv]``; ``sink [H]``: a logit column a head whose probability is
    dropped)."""
    B, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    rep = H // KH
    kc = jnp.repeat(k_cache, rep, axis=2) if rep > 1 else k_cache
    vc = jnp.repeat(v_cache, rep, axis=2) if rep > 1 else v_cache
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / (D ** 0.5)
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    p = _sink_softmax(s, mask, sink)
    return jnp.einsum("bhs,bshd->bhd", p,
                      vc.astype(jnp.float32)).astype(q.dtype)
