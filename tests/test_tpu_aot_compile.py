"""The main path's Pallas kernels, compiled for a TPU that is described
and not attached (``on-chip-measurement`` guide, section 2, third
rehearsal). Interpret mode — what every other kernel test here runs —
accepts block shapes and VMEM footprints the chip's compiler refuses;
these cases raise exactly what the chip would raise, at the widths the
smoke (``chip_smoke.py``) serves and trains, for no chip time.

Nothing executes: a pass says the kernel lowers and fits, never that it
computes the right thing (the smoke's kernel-vs-reference phase does).
"""
import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.ops.pallas import layer_norm as ln

# GPT-2 1.3B serving geometry: 16 heads x D=128, block 128, 8 slots,
# 1024-token context, 4-token speculation window, 256-token chunk; the
# kernels attend the second layer of a two-layer pool
S, MB, BS, NB, K, C = 8, 8, 128, 65, 4, 256
POOL_LAYERS, LAYER = 2, 1
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def chips():
    """The four described devices of a v5e 2x2; the persistent compile
    cache is off for the module (an entry compiled here cannot be read
    back without a chip, and the next run would warn about it)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this box
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _paged(kind, q_shape, table_shape, bound_shape, KH=16, D=128,
           int8=False):
    """One paged kernel (``decode`` / ``verify`` / ``chunk``) with its
    operands as shapes, the pool as ``PagedKVCache`` stores it: q, k
    pool, v pool ``[L, NB, BS, KH*D]``, table, bound[, k_scale, v_scale
    ``[L, NB, KH, BS]``]. ``q_shape`` is given without its trailing
    ``(H, D)``."""
    kernel = getattr(da, f"paged_{kind}_attention")
    pool = ((POOL_LAYERS, NB, BS, KH * D), jnp.int8 if int8 else BF16)
    scales = ([((POOL_LAYERS, NB, KH, BS), jnp.float32)] * 2 if int8
              else [])

    def fn(q, k, v, table, bound, *sc):
        return kernel(q, k, v, table, bound, interpret=False, layer=LAYER,
                      **dict(zip(("k_scale", "v_scale"), sc)))
    return fn, [((*q_shape, 16, D), BF16), pool, pool,
                (table_shape, jnp.int32), (bound_shape, jnp.int32), *scales]


_paged_decode = functools.partial(_paged, "decode", (S,), (S, MB), (S,))
_paged_verify = functools.partial(_paged, "verify", (S, K), (S, MB), (S,))
_paged_chunk = functools.partial(_paged, "chunk", (C,), (MB,), ())


def _dense_decode():
    fn = functools.partial(da.decode_attention, block_k=128,
                           interpret=False)
    cache = ((S, MB * BS, 16, 128), BF16)
    return fn, [((S, 16, 128), BF16), cache, cache, ((S,), jnp.int32)]


def _flash(grad, T=1024, causal=True):
    qkv = ((2, T, 16 * 1024 // T, 128), BF16)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()
    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd), [qkv] * 3


def _layer_norm():
    def loss(x, w, b):
        return ln.fused_layer_norm(x, w, b).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), [
        ((2048, 2048), BF16), ((2048,), jnp.float32),
        ((2048,), jnp.float32)]


def _retention(kind):
    """The power retention kernels at Brumby-14B's widths (8 key/value
    heads of 128 with 5 query heads each, 32 slots; a 1024-token bucket
    in chunks of 256), the state pool float32."""
    from deepspeed_tpu.ops.pallas import power_retention as pr
    slots, KH, G, d, T = 32, 8, 5, 128, 1024
    f32 = jnp.float32
    pool = [((slots, KH, pr.pair_rows(d), d, d), f32),
            ((slots, KH, pr.z_rows(d), d), f32)]
    if kind == "decode":
        fn = functools.partial(pr.power_retention_decode, eps=1e-6,
                               interpret=False)
        return fn, [((slots, KH, G, d), BF16), ((slots, KH, d), BF16),
                    ((slots, KH, d), BF16), ((slots, KH), f32),
                    ((slots,), jnp.bool_), *pool]

    def fn(q, k, v, g, n, S, z, slot):
        return pr.power_retention_prefill(q, k, v, g, n, S, z, slot,
                                          chunk=256, eps=1e-6,
                                          interpret=False)
    return fn, [((T, KH, G, d), BF16), ((T, KH, d), BF16),
                ((T, KH, d), BF16), ((T, KH), f32), ((), jnp.int32), *pool,
                ((), jnp.int32)]


# serve-longcat-flash-ep32-decode-batch's own geometry: 256 slots of 8
# blocks and the null block, 64 heads over rows of 512 + 64 values
LATENT = dict(slots=256, blocks=2049, heads=64, width=576, value_dim=512)


def _latent_decode():
    """The latent decode kernel at the LongCat cell's geometry: the
    query's latent part, one attention's pool ``[NB, W, BS]`` (donated:
    the kernel appends), the query's rotary part, the new rows, table,
    positions."""
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
    g = LATENT

    def fn(q_lat, pool, q_rope, rows, table, positions):
        return lda.paged_latent_decode_attention(
            q_lat, q_rope, rows, pool, table, positions,
            scale=192 ** -0.5, interpret=False)
    fn.donate = (1,)
    rope = g["width"] - g["value_dim"]
    return fn, [((g["slots"], g["heads"], g["value_dim"]), BF16),
                ((g["blocks"], g["width"], BS), BF16),
                ((g["slots"], g["heads"], rope), BF16),
                ((g["slots"], g["width"]), BF16),
                ((g["slots"], MB), jnp.int32), ((g["slots"],), jnp.int32)]


# serve-gigachat3-ep16-shared-context-batch's own geometry: 48 slots of
# 268 blocks over a pool of 2560 and the null block, chunks of 1024 rows,
# 64 heads of 128 + 64 wide keys and 192-wide values over 512 + 64 rows
GIGACHAT = dict(slots=48, blocks=2561, table=268, chunk=1024, heads=64,
                width=576, rank=512, nope=128, v_dim=192)


def _latent_chunk():
    """The prompt-chunk kernel at the GigaChat cell's geometry: q ``[H,
    C, Dn + Dr]``, one attention's pool ``[NB, W, BS]``, the slot's
    table, start, W_kb ``[H, Dn, R]``, W_vb ``[H, Dv, R]``."""
    from deepspeed_tpu.ops.pallas import latent_chunk_attention as lca
    g = GIGACHAT
    fn = functools.partial(lca.latent_chunk_attention, scale=0.1,
                           interpret=False)
    return fn, [((g["heads"], g["chunk"], g["width"] - g["rank"]
                  + g["nope"]), BF16),
                ((g["blocks"], g["width"], BS), BF16),
                ((g["table"],), jnp.int32), ((), jnp.int32),
                ((g["heads"], g["nope"], g["rank"]), BF16),
                ((g["heads"], g["v_dim"], g["rank"]), BF16)]


# serve-laguna-xs2-ep8-mixed-context-batch's own geometry: 96 slots, a
# 640-row ring a slot (5 blocks) of 8 kv heads x 128, groups of 8 query
# heads on the window layers; prompts to 8192 tokens
LAGUNA = dict(slots=96, ring_blocks=5, window=512, kv_heads=8,
              window_heads=64, full_heads=48, prompt=8192)


def _window_decode():
    """The window walk at the Laguna cell's geometry: q, the second of
    two window layers' rings ``[Lw, S*RB, BS, KH*D]``, lengths."""
    g = LAGUNA
    ring = ((2, g["slots"] * g["ring_blocks"], BS, g["kv_heads"] * 128),
            BF16)
    fn = functools.partial(da.paged_window_decode_attention,
                           window=g["window"], layer=1, interpret=False)
    return fn, [((g["slots"], g["window_heads"], 128), BF16), ring, ring,
                ((g["slots"],), jnp.int32)]


def _one_token_walk(slots, heads, kv_heads, D, Dv, blocks, table,
                    window=0, sink=False):
    """The one-token decode kernel at a cell's own geometry: q, the last
    of three layers' pools ``[L, NB, BS, KH*D]`` / ``[.., KH*Dv]`` (a
    window layer's rings, ``table`` blocks a slot), table (a pool's),
    lengths[, sink]."""
    if window:
        kernel = functools.partial(da.paged_window_decode_attention,
                                   window=window)
        blocks, tables = slots * table, []
    else:
        kernel, tables = da.paged_decode_attention, [
            ((slots, table), jnp.int32)]

    def fn(q, k, v, *rest):
        rest, sinks = (rest[:-1], rest[-1]) if sink else (rest, None)
        return kernel(q, k, v, *rest, interpret=False, layer=2, sink=sinks)
    return fn, [((slots, heads, D), BF16),
                ((3, blocks, BS, kv_heads * D), BF16),
                ((3, blocks, BS, kv_heads * Dv), BF16), *tables,
                ((slots,), jnp.int32),
                *([((heads,), jnp.float32)] if sink else [])]


# the cells whose one-token walk attends several table entries a loop
# iteration: the kernel's name, K + V bytes a table entry, the entries
# the rule gives them (``paged_decode_entries_per_iteration``)
WALKS = {
    "paged_decode-mimo-full": ("paged_decode_attention", 327680, 3),
    "paged_window_decode-mimo-ring": ("paged_window_decode_attention",
                                      655360, 2),
    "paged_decode-laguna-full": ("paged_decode_attention", 524288, 2),
    "paged_window_decode-ring": ("paged_window_decode_attention", 524288,
                                 2),
    "paged_decode-fp": ("paged_decode_attention", 1048576, 1),
}


def _flash_window():
    """The windowed flash forward at the cell's longest bucket."""
    g = LAGUNA
    kv = ((1, g["prompt"], g["kv_heads"], 128), BF16)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=g["window"])
    return fwd, [((1, g["prompt"], g["window_heads"], 128), BF16), kv, kv]


def _aligned(R, X, E, Fe):
    """The row tile an expert's two matmuls share and the buffer ``R``
    landed picks are laid out in on its boundaries, as
    ``held_experts._align`` builds it."""
    from deepspeed_tpu.model_implementations.held_experts import (
        expert_row_tile)
    tm = expert_row_tile(R, X, E, Fe, 2)
    return tm, gm.aligned_rows(R, X, tm)


def _grouped_matmul(R, X, E, Fe):
    """The small-tile grouped matmul as the held experts' layer calls it
    (``w_in``, the SwiGLU, ``w_out``) over the aligned buffer of a decode
    program's ``R`` landed picks and ``X`` held experts at a cell's full
    widths."""
    tm, rows = _aligned(R, X, E, Fe)

    def fwd(xs, sizes, w_in, w_out):
        gu = gm.grouped_matmul(xs, w_in, sizes, tm).astype(jnp.float32)
        h = jax.nn.silu(gu[:, :Fe]) * gu[:, Fe:]
        return gm.grouped_matmul(h.astype(xs.dtype), w_out, sizes, tm)
    return fwd, [((rows, E), BF16), ((X,), jnp.int32),
                 ((X, E, 2 * Fe), BF16), ((X, Fe, E), BF16)]


def _grouped_matmul_ungated(R, X, E, Fe):
    """The same kernel under UNGATED relu² experts (``w_in [X, E, Fe]``)
    ``Fe`` wide: 1920, as the Nemotron-H family stores its 1856."""
    tm, rows = _aligned(R, X, E, Fe)

    def fwd(xs, sizes, w_in, w_out):
        u = gm.grouped_matmul(xs, w_in, sizes, tm).astype(jnp.float32)
        h = jnp.square(jax.nn.relu(u))
        return gm.grouped_matmul(h.astype(xs.dtype), w_out, sizes, tm)
    return fwd, [((rows, E), BF16), ((X,), jnp.int32),
                 ((X, E, Fe), BF16), ((X, Fe, E), BF16)]


CASES = {
    "grouped-matmul-nemotron-decode": functools.partial(
        _grouped_matmul_ungated, 1024, 64, 2688, 1920),
    "grouped-matmul-granite-decode": functools.partial(
        _grouped_matmul, 640, 36, 4096, 768),
    "grouped-matmul-laguna-decode": functools.partial(
        _grouped_matmul, 256, 32, 2048, 512),
    "grouped-matmul-gigachat-decode": functools.partial(
        _grouped_matmul, 128, 16, 7168, 2048),
    "grouped-matmul-longcat-decode": functools.partial(
        _grouped_matmul, 128, 16, 6144, 2048),
    "grouped-matmul-longcat-rider": functools.partial(
        _grouped_matmul, 256, 16, 6144, 2048),
    "paged_window_decode-ring": _window_decode,
    "paged_decode-mimo-full": functools.partial(
        _one_token_walk, 96, 64, 4, 192, 128, 5401, 96),
    "paged_window_decode-mimo-ring": functools.partial(
        _one_token_walk, 96, 64, 8, 192, 128, 0, 2, window=128, sink=True),
    "paged_decode-laguna-full": functools.partial(
        _one_token_walk, 96, 48, 8, 128, 128, 3201, 80),
    "flash-window-fwd": _flash_window,
    "latent-decode": _latent_decode,
    "latent-chunk": _latent_chunk,
    "retention_decode": functools.partial(_retention, "decode"),
    "retention_prefill": functools.partial(_retention, "prefill"),
    "decode": _dense_decode,
    "paged_decode-fp": _paged_decode,
    "paged_decode-int8": functools.partial(_paged_decode, int8=True),
    "paged_chunk-fp": _paged_chunk,
    "paged_chunk-int8": functools.partial(_paged_chunk, int8=True),
    "paged_verify-fp": _paged_verify,
    "paged_verify-int8": functools.partial(_paged_verify, int8=True),
    "paged_decode-gqa-kh4": functools.partial(_paged_decode, KH=4),
    "paged_verify-gqa-kh4": functools.partial(_paged_verify, KH=4),
    "paged_chunk-gqa-kh4-int8": functools.partial(_paged_chunk, KH=4,
                                                  int8=True),
    "paged_decode-d64-int8": functools.partial(_paged_decode, D=64,
                                               int8=True),
    "flash-fwd": functools.partial(_flash, grad=False),
    "flash-fwd-bwd": functools.partial(_flash, grad=True),
    # a head too long to write out, and with no mask: a row of 16 score
    # blocks written out under the looped q (k) axis
    "flash-fwd-bwd-noncausal-t4096": functools.partial(
        _flash, grad=True, T=4096, causal=False),
    "layer_norm-fwd-bwd": _layer_norm,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, chips, monkeypatch):
    # flash / layer-norm read the backend to pick interpret mode; the
    # process is on the CPU, the compile target is not
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    monkeypatch.setattr(ln, "_should_interpret", lambda: False)
    monkeypatch.setattr(gm, "_should_interpret", lambda: False)
    fn, shapes = CASES[case]()
    one = SingleDeviceSharding(chips[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one)
            for shape, dtype in shapes]
    da._paged_call.cache_clear()
    compiled = jax.jit(fn, donate_argnums=getattr(fn, "donate", ())
                       ).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if case in WALKS:
        # the walk the rule gave these shapes, under the kernel's own
        # name, inside the scoped VMEM every kernel gets (a call that
        # asks for more is written on every instruction)
        from deepspeed_tpu.telemetry import compile_watch
        from deepspeed_tpu.telemetry.registry import get_registry
        name, slab, entries = WALKS[case]
        assert get_registry().gauge(
            "paged_decode_entries_per_iteration", labels={
                "kernel": name, "slab_bytes": str(slab)}).value == entries
        assert set(compile_watch.parse_scopes(text)[1].values()) == {name}
        assert set(re.findall(r'"scoped_memory_configs":\[([^\]]*)\]',
                              text)) == {""}
    if case.startswith("grouped-matmul-"):
        # both matmuls are the small-tile kernel, and neither asked for
        # more than the default scoped VMEM (a call that does re-lays the
        # fusions of the whole program around it: the compiler then
        # writes what it was given on every instruction)
        from deepspeed_tpu.telemetry import compile_watch
        _, kernels = compile_watch.parse_scopes(text)
        assert sorted(kernels.values()) == [gm.NAME] * 2
        assert set(re.findall(r'"scoped_memory_configs":\[([^\]]*)\]',
                              text)) == {""}
        # the weights go to the kernel as they are stored: no instruction
        # writes a whole ``w_in`` or ``w_out`` in another layout (a
        # ``copy-start`` / ``copy-done`` pair is the compiler's prefetch
        # of Laguna's 64 MiB ``w_out`` into VMEM, as stored)
        weights = {shape for shape, _ in shapes[2:]}
        assert not [c for c in _copies(
            text, lambda dims, nbytes: dims in weights)
            if not c.endswith(("copy-start", "copy-done"))]
    if case.startswith(("paged_", "latent-")):
        # the pool goes to the kernel as it is stored: nothing as large
        # as one layer of K (one attention's latent pool), and nothing
        # shaped like a layer's scale tiles, is written on the way in
        (*_, NB_, BS_, W), dtype = shapes[1]
        layer = NB_ * BS_ * W * jnp.dtype(dtype).itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < layer
        tiles = set()
        if len(shapes) > 5 and len(shapes[-1][0]) == 4:
            L_, _, KH_, _ = whole = shapes[-1][0]
            tiles = {whole, whole[1:], (L_ * NB_, KH_, BS_)}
        assert not _copies(
            text, lambda dims, nbytes: nbytes >= layer or dims in tiles)


# an instruction's name, output type, dims and opcode in compiled text
_OUT = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\][^ ]* ([\w\-]+)\(")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1}
# what moves no byte of its operand, and what updates the pool in place
_FREE = {"parameter", "get-tuple-element", "tuple", "bitcast"}
_IN_PLACE = {"fusion", "scatter", "dynamic-update-slice"}


def _without_grouped_matmuls(kernels, scopes):
    """``kernels`` less the expert layers' grouped matmuls: the compiler's
    (``ragged-dot-*``, which carry no scope) and ours, which must sit
    under the scope ``moe_experts`` (the trace readers sum that scope)."""
    tiled = [k for k, v in kernels.items() if v == gm.NAME]
    assert all(scopes[k].rsplit("/", 1)[-1] == "moe_experts" for k in tiled)
    return {k: v for k, v in kernels.items()
            if v != gm.NAME and not v.startswith("ragged")}


def _copies(text, is_big, pool_dims=None, kernels=()):
    """The instructions of a compiled program whose output ``is_big(dims,
    nbytes)`` and that are neither free (a parameter, a bitcast), a
    kernel call, nor an in-place write of the whole pool
    (``pool_dims``): each is a copy of a layer of the pool, or more."""
    out = []
    for line in text.splitlines():
        m = _OUT.match(line)
        if m is None or m.group(2) not in _ITEMSIZE:
            continue
        name, dtype, dims, op = m.groups()
        dims = tuple(int(d) for d in dims.split(",") if d)
        if (op in _FREE or name in kernels
                # a matmul fusion's operand re-typed in place: the
                # compiler names such a computation itself
                or "calls=%bitcast_fusion" in line
                or not is_big(dims, math.prod(dims) * _ITEMSIZE[dtype])
                or (op in _IN_PLACE and dims == pool_dims)):
            continue
        out.append(f"{name} = {dtype}{list(dims)} {op}")
    return out


@pytest.mark.parametrize("kind", ["decode", "verify", "chunk"])
def test_kernel_maps_over_a_mesh(chips, kind):
    """GSPMD cannot partition a Mosaic call ("wrap the call in a
    shard_map"): on four devices a paged kernel goes through
    ``map_kernel`` over the kv-head axis (the major part of the pool's
    lane dim), as tensor-parallel serving lays the pool out — and the
    compiler must not have gathered the pool to make that work. A shard
    walks the same blocks over its own four heads' lanes."""
    from deepspeed_tpu.utils.sharding import map_kernel
    mesh = Mesh(np.asarray(chips).reshape(1, 1, 4),
                ("expert", "seq", "tensor"))
    fn, shapes = {"decode": _paged_decode, "verify": _paged_verify,
                  "chunk": _paged_chunk}[kind]()
    q = P(*[None] * (len(shapes[0][0]) - 2), "tensor", None)
    pool = P(None, None, None, "tensor")
    specs = (q, pool, pool, P(), P())
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for (shape, dtype), spec in zip(shapes, specs)]
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(fn).lower(*args).compile()
    text = jax.jit(map_kernel(fn, mesh, specs, q)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text and "all-gather(" not in text


# ------------------------------------------------ names on the device
# (ISSUE 24) What a device trace shows of a program is its module name,
# its instructions' names and nothing else: the watch names the module,
# ``pallas_call(name=...)`` the kernels, and the compile watch's scope
# table maps instructions to the ``jax.named_scope`` layer boundaries.
# Read back here from the text of programs compiled for the chip.

L_NAMES = 2
SERVE_SCOPES = {"embed", "ln", "attn_qkv", "kv_write", "attn_kernel",
                "attn_out", "mlp", "lm_head", "sample"}
# serve-gpt2-1.3b-batch's own geometry: 24 layers, 32 slots of 8 blocks
# and the null block, d_model 2048 = 16 x 128, GPT-2's vocabulary
CELL = dict(layers=24, slots=32, blocks=257, heads=16, embd=2048,
            vocab=50257)


def _abstract(tree, sharding):
    """A tree of arrays (or of shapes) as shapes on ``sharding``."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _serve_program(kind, device, layers=L_NAMES, slots=S, blocks=NB,
                   heads=2, embd=256, vocab=512, quantized=False):
    """``(jitted program named as the server names it, its name,
    abstract arguments)`` at d_head 128, block 128; 8 slots of a small
    model unless told otherwise."""
    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, init_params)
    from deepspeed_tpu.telemetry import compile_watch
    one = SingleDeviceSharding(device)
    cfg = InferenceTransformerConfig(
        vocab_size=vocab, n_positions=1024, n_embd=embd, n_layer=layers,
        n_head=heads, dtype=BF16)

    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_paged_cache(
        layers, slots, blocks, BS, MB, heads, 128, BF16,
        quantized=quantized)))
    fn, name, args = {
        "decode": (Srv._decode_fn, "serve_decode",
                   (params, arr((slots,)), cache,
                    arr((slots,), jnp.bool_))),
        "prefill": (Srv._prefill_fn, "serve_prefill",
                    (params, arr((1, C)), arr((1,)), cache, arr(()))),
        "chunk": (Srv._chunk_fn, "serve_prefill_chunk",
                  (params, arr((1, C)), arr(()), arr((1,)), cache,
                   arr(()))),
        "verify": (Srv._verify_fn, "serve_spec_verify",
                   (params, arr((slots, K)), cache)),
    }[kind]
    prog = jax.jit(compile_watch._named(
        functools.partial(fn, cfg=cfg, mesh=None), name),
        donate_argnames=("cache",))
    return prog, name, args


@pytest.mark.parametrize("kind,kernel,geometry", [
    ("decode", "paged_decode_attention", {}),
    ("prefill", "flash_attention_fwd", {}),
    ("chunk", "paged_chunk_attention", {}),
    ("verify", "paged_verify_attention", {}),
    ("decode", "paged_decode_attention", CELL),
    ("decode", "paged_decode_attention", {"quantized": True}),
], ids=["decode", "prefill", "chunk", "verify", "decode-cell",
        "decode-int8"])
def test_serving_programs_carry_their_names(chips, monkeypatch, kind,
                                            kernel, geometry):
    """Module name, kernel name and every layer scope of a serving
    program, read back from its compiled text — and that the three
    paged programs attend the pool where it lies: no instruction's scope
    is ``kv_read`` (only the XLA fallback gathers cut the pool), and
    apart from the kernel calls and the in-place writes of the pool
    nothing writes as much as one layer's K; so the program needs no
    temporary of that size either (the cell's 24-layer decode program
    held 2.8 GB of them when a layer was cut out for every call)."""
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    prog, name, args = _serve_program(kind, chips[0], **geometry)
    compiled = prog.lower(*args).compile()
    text = compiled.as_text()
    assert f"HloModule jit_{name}" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    layers = geometry.get("layers", L_NAMES)
    assert set(kernels.values()) == {kernel}
    assert len(kernels) == layers             # one call a layer
    assert all(scopes[k] == "attn_kernel" for k in kernels)
    innermost = {v.rsplit("/", 1)[-1] for v in scopes.values() if v}
    assert innermost >= SERVE_SCOPES, SERVE_SCOPES - innermost
    if kind != "prefill":
        assert "kv_read" not in innermost
    # an int8 pool's WRITERS still cut a layer out to requantize it (on
    # the parent too; PERF.md section 7): that case only shows that the
    # program compiles around the kernel's scale-tile streams
    if kind != "prefill" and not geometry.get("quantized"):
        pool = args[2 if kind != "chunk" else 4].k
        layer_k = math.prod(pool.shape[1:]) * pool.dtype.itemsize
        if not geometry:    # at the cell's widths its weights are larger
            assert not _copies(text, lambda dims, nbytes: nbytes >= layer_k,
                               pool_dims=pool.shape, kernels=kernels)
        assert not _copies(
            text, lambda dims, nbytes: dims[-2:] == pool.shape[-2:]
            and nbytes >= layer_k, pool_dims=pool.shape)
        assert compiled.memory_analysis().temp_size_in_bytes < layer_k


LATENT_SCOPES = {"embed", "ln", "mla_qkv", "latent_write", "mla_attn",
                 "attn_out", "dense_ffn", "moe_router", "moe_dispatch",
                 "moe_experts", "moe_combine", "lm_head", "sample"}
# a decode step has no pool writer of its own: the kernel appends
LATENT_DECODE_SCOPES = LATENT_SCOPES - {"latent_write"}


def _appends_in_the_kernel(text, kernels, scopes, attentions, slots, width):
    """A compiled program that decodes over a latent pool: one
    ``paged_latent_decode_attention`` an attention under the scope
    ``mla_attn``, each with its pool aliased in and out, no
    ``paged_latent_append``, and no array with the rows' ``W`` values
    down the sublanes over one live lane (``[S, W, 1]``)."""
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
    ours = {k: name for k, name in kernels.items()
            if name.startswith("paged_latent")}
    assert sorted(ours.values()) == [lda.NAME] * attentions
    assert all(scopes[k].rsplit("/", 1)[-1] == "mla_attn" for k in ours)
    calls = [line for line in text.splitlines() if " custom-call(" in line
             and line.split(" = ")[0].split()[-1].lstrip("%") in ours]
    assert len(calls) == attentions
    # output 1, the pool, is operand 5 (after positions, tables, the
    # query's two parts and the rows)
    assert all("output_to_operand_aliasing={{1}: (5, {})}" in line
               for line in calls)
    assert f"[{slots},{width},1]" not in text


def test_latent_decode_program_walks_the_pool_where_it_lies(chips,
                                                            monkeypatch):
    """LongCat's ``serve_decode`` at the cell's widths, slots and pool
    (two of its four layers, a small vocabulary, the cell's sixteen held
    experts), read back from its compiled text: module, kernel and scope
    names; one ``paged_latent_decode_attention`` an attention, which
    appends the step's rows to its donated pool in place (no
    ``paged_latent_append``, no ``latent_write`` scope, no ``[S, W, 1]``
    operand); two ``held_experts_grouped_matmul`` an expert layer and
    branch under the scope ``moe_experts`` and no grouped matmul of the
    compiler's; and apart from those calls nothing writes as much as one
    attention's pool: no copy, no transpose, no temporary of that
    size."""
    from deepspeed_tpu.inference.kv_cache import init_latent_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations import longcat_flash as lf
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(chips[0])
    g = LATENT
    cfg = lf.LongcatFlashConfig(vocab_size=2048, num_layers=2,
                                experts_held=(0, 16))
    assert (cfg.num_attention_heads, cfg.latent_width, cfg.kv_lora_rank) == (
        g["heads"], g["width"], g["value_dim"])

    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: lf.init_params(jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_latent_paged_cache(
        cfg.attentions, g["slots"], g["blocks"], BS, MB, cfg.latent_width,
        aux_shape=cfg.aux_shape)))
    lda._latent_call.cache_clear()
    lowered = jax.jit(compile_watch._named(
        functools.partial(Srv._decode_fn, cfg=cfg, mesh=None),
        "serve_decode"), donate_argnames=("cache",)).lower(
        params, arr((g["slots"],)), cache,
        arr((g["slots"],), jnp.bool_))
    compiled = lowered.compile()
    # four attentions, one signature: one kept call, found again thrice
    info = lda._latent_call.cache_info()
    assert (info.misses, info.hits) == (1, cfg.attentions - 1)
    text = compiled.as_text()
    assert "HloModule jit_serve_decode" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    # the expert layers' grouped matmuls: ``w_in`` and ``w_out`` in each
    # branch of ``held_experts_part`` (the 128-row buffer and the exact
    # ``T k`` fallback), lowered once a signature (a function of its own
    # in the module) and called a layer
    experts = [k for k, name in kernels.items() if name == gm.NAME]
    assert len(experts) == 4 * cfg.num_layers
    assert all(scopes[k].rsplit("/", 1)[-1] == "moe_experts"
               for k in experts)
    assert not [name for name in kernels.values()
                if name.startswith("ragged")]
    assert lowered.as_text().count(f'kernel_name = "{gm.NAME}"') == 4
    _appends_in_the_kernel(text, kernels, scopes, cfg.attentions,
                           g["slots"], g["width"])
    innermost = {v.rsplit("/", 1)[-1] for v in scopes.values() if v}
    assert innermost >= LATENT_DECODE_SCOPES, (
        LATENT_DECODE_SCOPES - innermost)
    assert "latent_write" not in innermost
    pool = cache.rows[0]
    pool_bytes = math.prod(pool.shape) * pool.dtype.itemsize
    assert not _copies(
        text, lambda dims, nbytes: nbytes >= pool_bytes
        or sorted(dims[-2:]) == sorted(pool.shape[-2:]), kernels=kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.parametrize("bucket", [128, 256])
def test_latent_decode_admit_program_updates_the_donated_pool(
        chips, monkeypatch, bucket):
    """LongCat's ``serve_decode_admit`` (the decode step and one admitted
    prompt in one forward) at the cell's widths, 256 slots, pool and
    both prompt buckets (two of its four layers, a small vocabulary, two
    held experts), read back from its compiled text: an attention is one
    ``paged_latent_decode_attention`` for the decode rows, which appends
    them too (no ``paged_latent_append``, no ``[S, W, 1]`` operand), and
    one ``flash_attention_fwd`` for the prompt, whose rows the scatter
    under ``latent_write`` stores;
    every buffer of the donated cache comes back in the buffer it came
    in; and nothing writes as much as one attention's pool or converts
    its layout, so no second copy of the pool is planned."""
    from deepspeed_tpu.inference.kv_cache import init_latent_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations import longcat_flash as lf
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    one = SingleDeviceSharding(chips[0])
    g = LATENT
    cfg = lf.LongcatFlashConfig(vocab_size=2048, num_layers=2,
                                experts_held=(0, 2))
    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: lf.init_params(jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_latent_paged_cache(
        cfg.attentions, g["slots"], g["blocks"], BS, MB, cfg.latent_width,
        aux_shape=cfg.aux_shape)))
    compiled = jax.jit(compile_watch._named(
        functools.partial(Srv._decode_admit_fn, cfg=cfg, mesh=None),
        "serve_decode_admit"), donate_argnames=("cache",)).lower(
        params, arr((g["slots"],)), cache, arr((g["slots"],), jnp.bool_),
        arr((1, bucket)), arr((1,)), arr(())).compile()
    text = compiled.as_text()
    assert "HloModule jit_serve_decode_admit" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    _appends_in_the_kernel(text, kernels, scopes, cfg.attentions,
                           g["slots"], g["width"])
    assert sorted(name for name in kernels.values()
                  if name.startswith("flash_")) == (
        ["flash_attention_fwd"] * cfg.attentions)
    innermost = {v.rsplit("/", 1)[-1] for v in scopes.values() if v}
    assert innermost >= LATENT_SCOPES, LATENT_SCOPES - innermost
    # the cache is donated: every one of its buffers is aliased to an
    # output (pools, tables, lengths, counters)
    header = text[:text.index("\n\n")]
    donated = len(jax.tree.leaves(cache))
    assert header.count("may-alias") + header.count("must-alias") >= donated
    pool = cache.rows[0]
    pool_bytes = math.prod(pool.shape) * pool.dtype.itemsize
    assert not _copies(
        text, lambda dims, nbytes: nbytes >= pool_bytes
        or (sorted(dims[-2:]) == sorted(pool.shape[-2:])
            and math.prod(dims[:-2]) > bucket // BS),
        pool_dims=pool.shape, kernels=kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_gigachat_programs_read_the_latent_pool_where_it_lies(
        chips, monkeypatch, kind):
    """GigaChat3's ``serve_decode`` and ``serve_prefill_chunk`` at the
    cell's widths, slots, pool, table and chunk (one dense and one
    expert layer of its six, a small vocabulary, two held experts), read
    back from their compiled text: module, kernel and scope names; one
    attention kernel an attention (``latent_chunk_attention``, or
    ``paged_latent_decode_attention``, which appends the step's rows to
    its aliased pool itself: no ``paged_latent_append``, no ``[S, W,
    1]`` operand); and apart from the kernels' calls and
    the in-place writes of the donated pool nothing writes as much as
    one attention's pool, nothing converts a pool's layout, and nothing
    is as large as the K or V of a slot's whole context."""
    from deepspeed_tpu.inference.kv_cache import init_latent_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations import deepseek_v3 as dv
    from deepspeed_tpu.ops.pallas import latent_chunk_attention as lca
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(chips[0])
    g = GIGACHAT
    cfg = dv.DeepseekV3Config(
        vocab_size=2048, num_hidden_layers=2, first_k_dense_replace=1,
        num_attention_heads=g["heads"], v_head_dim=g["v_dim"],
        rope_theta=100000.0, rope_factor=64.0, experts_held=(0, 2))
    assert (cfg.latent_width, cfg.kv_lora_rank, cfg.qk_nope_head_dim) == (
        g["width"], g["rank"], g["nope"])
    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: dv.init_params(jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_latent_paged_cache(
        cfg.attentions, g["slots"], g["blocks"], BS, g["table"],
        cfg.latent_width, aux_shape=cfg.aux_shape)))
    fn, name, args, kernel = {
        "decode": (Srv._decode_fn, "serve_decode",
                   (params, arr((g["slots"],)), cache,
                    arr((g["slots"],), jnp.bool_)), lda.NAME),
        "chunk": (Srv._chunk_fn, "serve_prefill_chunk",
                  (params, arr((1, g["chunk"])), arr(()), arr((1,)), cache,
                   arr(())), lca.NAME)}[kind]
    compiled = jax.jit(compile_watch._named(
        functools.partial(fn, cfg=cfg, mesh=None), name),
        donate_argnames=("cache",)).lower(*args).compile()
    text = compiled.as_text()
    assert f"HloModule jit_{name}" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    ours = {k: v for k, v in kernels.items()
            if v.startswith(("paged_latent", "latent_"))}
    assert sorted(ours.values()) == [kernel] * cfg.attentions
    assert all(scopes[k].rsplit("/", 1)[-1] == "mla_attn" for k in ours)
    innermost = {v.rsplit("/", 1)[-1] for v in scopes.values() if v}
    expected = (LATENT_DECODE_SCOPES if kind == "decode"
                else LATENT_SCOPES) | {"moe_shared"}
    assert innermost >= expected, expected - innermost
    if kind == "decode":
        # the kernel appends the step's rows: the program has no writer
        _appends_in_the_kernel(text, kernels, scopes, cfg.attentions,
                               g["slots"], g["width"])
        assert "latent_write" not in innermost
    pool = cache.rows[0]
    pool_bytes = math.prod(pool.shape) * pool.dtype.itemsize
    # K (or V) of one slot's whole context, every head, bfloat16, is
    # larger still than one attention's pool: one bound holds both
    assert g["table"] * BS * g["heads"] * g["nope"] * 2 > pool_bytes
    # (a chunk's own rows, 8 blocks of them, are turned to the pool's
    # layout before they are written: that is no pool's conversion)
    own = g["chunk"] // BS
    assert not _copies(
        text, lambda dims, nbytes: nbytes >= pool_bytes
        or (sorted(dims[-2:]) == sorted(pool.shape[-2:])
            and math.prod(dims[:-2]) > own),
        pool_dims=pool.shape, kernels=kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


RETENTION_SCOPES = {"embed", "ln", "ret_qkvg", "ret_state", "ret_out",
                    "mlp", "lm_head", "sample"}


@pytest.mark.parametrize("kind,kernel", [
    ("decode", "power_retention_decode"),
    ("prefill", "power_retention_prefill")])
def test_retention_programs_update_the_state_in_place(chips, monkeypatch,
                                                      kind, kernel):
    """The state pool's two serving programs at the Brumby cell's widths
    (two layers, a small vocabulary), read back from their compiled
    text: module, kernel and scope names; ONE kernel call a layer (the
    state crosses HBM once each way: no second pass reads it for the
    queries); and apart from those calls no instruction writes anything
    shaped like a slot's state or as large as a layer's pool, and the
    program's temporaries are a few slots' worth at most (a prefill
    writes its slot of the donated pool in place)."""
    from deepspeed_tpu.inference.kv_cache import init_recurrent_state_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations import brumby
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(chips[0])
    layers, slots = 2, 32
    cfg = brumby.BrumbyConfig(vocab_size=2048, num_hidden_layers=layers)

    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: brumby.init_params(jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_recurrent_state_cache(
        layers, slots, *cfg.state_shapes, aux_shape=cfg.aux_shape)))
    fn, name, args = {
        "decode": (Srv._decode_fn, "serve_decode",
                   (params, arr((slots,)), cache,
                    arr((slots,), jnp.bool_))),
        "prefill": (Srv._prefill_fn, "serve_prefill",
                    (params, arr((1, 1024)), arr((1,)), cache, arr(()))),
    }[kind]
    compiled = jax.jit(compile_watch._named(
        functools.partial(fn, cfg=cfg, mesh=None), name),
        donate_argnames=("cache",)).lower(*args).compile()
    text = compiled.as_text()
    assert f"HloModule jit_{name}" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    assert set(kernels.values()) == {kernel}
    assert len(kernels) == layers
    assert all(scopes[k] == "ret_state" for k in kernels)
    innermost = {v.rsplit("/", 1)[-1] for v in scopes.values() if v}
    assert innermost >= RETENTION_SCOPES, RETENTION_SCOPES - innermost
    pool = cache.S[0]
    layer_pool = math.prod(pool.shape) * pool.dtype.itemsize
    assert not _copies(
        text, lambda dims, nbytes: dims[-3:] == pool.shape[-3:]
        or nbytes >= layer_pool, kernels=kernels)
    # decode: less than ONE slot's state of one layer; prefill: the
    # activations of its 1024 tokens (74 MB), far under a layer's pool
    room = cfg.state_bytes * (1 if kind == "decode" else 4)
    assert compiled.memory_analysis().temp_size_in_bytes < room


LAGUNA_SCOPES = {"embed", "ln", "attn_full", "attn_window", "kv_write",
                 "dense_ffn", "moe_router", "moe_dispatch", "moe_experts",
                 "moe_combine", "moe_shared", "lm_head", "sample"}


@pytest.mark.parametrize("kind,kernels_want", [
    ("decode", {"paged_decode_attention": 2,
                "paged_window_decode_attention": 3}),
    ("prefill", {"flash_attention_fwd": 2, "flash_attention_window_fwd": 3}),
])
def test_window_and_full_layers_share_one_program(chips, monkeypatch, kind,
                                                  kernels_want):
    """Laguna's two serving programs at the cell's widths, slots, rings,
    pool and longest prompt (a dense full layer, three sparse window
    layers and a sparse full layer; a small vocabulary and two held
    experts), read back from their compiled text: module, kernel
    and scope names; one kernel call a layer, by the layer's kind; no
    ``kv_read`` (nothing gathers the pool or a ring); apart from the
    kernel calls and the in-place writes nothing as large as one window
    layer's rings or one layer of the pool is written, so no layout
    conversion sits around a kernel call; and the decode program needs
    no temporary of that size."""
    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations import laguna as lg
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    g = LAGUNA
    one = SingleDeviceSharding(chips[0])
    cfg = lg.LagunaConfig(
        vocab_size=2048, num_hidden_layers=5,
        layer_types=(lg.FULL,) + (lg.WINDOW,) * 3 + (lg.FULL,),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        num_attention_heads_per_layer=(g["full_heads"],)
        + (g["window_heads"],) * 3 + (g["full_heads"],),
        rope_full=lg.RopeSpec(
            rope_theta=500000, rope_type="yarn", factor=64,
            original_max_position_embeddings=4096, beta_fast=64,
            partial_rotary_factor=0.5),
        rope_sliding=lg.RopeSpec(rope_theta=10000),
        experts_held=(0, 2))
    assert (cfg.kv_heads, cfg.head_dim, cfg.sliding_window) == (
        g["kv_heads"], 128, g["window"])
    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: lg.init_params(jax.random.PRNGKey(0), cfg)))
    blocks, span = 1 + 3200, 80
    cache = abstract(jax.eval_shape(lambda: init_paged_cache(
        cfg.n_layer, g["slots"], blocks, BS, span, cfg.kv_heads,
        cfg.head_dim, BF16, window_layers=cfg.window_layers,
        window=cfg.sliding_window, aux_shape=cfg.aux_shape)))
    assert cache.k.shape == (2, blocks, BS, 1024)
    assert cache.ring_k.shape == (3, g["slots"] * g["ring_blocks"], BS, 1024)
    fn, name, args = {
        "decode": (Srv._decode_fn, "serve_decode",
                   (params, arr((g["slots"],)), cache,
                    arr((g["slots"],), jnp.bool_))),
        "prefill": (Srv._prefill_fn, "serve_prefill",
                    (params, arr((1, g["prompt"])), arr((1,)), cache,
                     arr(()))),
    }[kind]
    da._paged_call.cache_clear()
    compiled = jax.jit(compile_watch._named(
        functools.partial(fn, cfg=cfg, mesh=None), name),
        donate_argnames=("cache",)).lower(*args).compile()
    text = compiled.as_text()
    assert f"HloModule jit_{name}" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    ours = _without_grouped_matmuls(kernels, scopes)
    counts = {v: list(ours.values()).count(v) for v in set(ours.values())}
    assert counts == kernels_want
    for k, v in ours.items():
        assert scopes[k].split("/")[0] == (
            "attn_window" if "window" in v else "attn_full"), (k, scopes[k])
    words = {w for v in scopes.values() if v for w in v.split("/")}
    assert words >= LAGUNA_SCOPES, LAGUNA_SCOPES - words
    assert "kv_read" not in words
    ring_layer = math.prod(cache.ring_k.shape[1:]) * 2
    pool_layer = math.prod(cache.k.shape[1:]) * 2
    stores = (cache.k.shape, cache.ring_k.shape)
    assert not _copies(
        text, lambda dims, nbytes: dims not in stores and nbytes >= min(
            ring_layer, pool_layer) and dims[-1:] == (1024,)
        and dims[-2:] == (BS, 1024), kernels=kernels)
    assert not [c for c in _copies(
        text, lambda dims, nbytes: dims in stores, kernels=kernels)
        if not any(op in c for op in _IN_PLACE)]
    if kind == "decode":
        assert compiled.memory_analysis().temp_size_in_bytes < ring_layer


MIMO_SCOPES = {"embed", "ln", "attn_full", "attn_window", "kv_write",
               "dense_ffn", "moe_router", "moe_dispatch", "moe_experts",
               "moe_combine", "lm_head", "sample"}
# serve-mimo-v2-flash-ep32-reasoning-batch's own geometry
MIMO = dict(slots=96, blocks=1 + 5400, span=96, prompt=4096, window=128)


@pytest.mark.parametrize("kind,kernels_want", [
    ("decode", {"paged_decode_attention": 3,
                "paged_window_decode_attention": 9}),
    ("prefill", {"flash_attention_fwd": 3, "flash_attention_window_fwd": 9}),
])
def test_mimo_programs_keep_four_row_widths_in_one_cache(chips, monkeypatch,
                                                         kind, kernels_want):
    """MiMo-V2-Flash's two serving programs at the cell's OWN sizes (the
    published widths, all 12 layers of the stage, 8 held experts, 19072
    vocabulary rows, 96 slots, 5400 blocks, the 4096-token bucket), read
    back from their compiled text: module, kernel and scope names, one
    kernel call a layer by the layer's kind (the decode kernel at K rows
    of 768 / 1536 lanes and V rows of 512 / 1024, heads of 192 sharing a
    block-diagonal product, the sink on the window layers); no
    ``kv_read``; apart from the kernel calls and the in-place writes
    nothing as large as one layer of the pool or of the rings is
    written; and the bytes the configuration file states: arguments of
    ``serve_decode`` and both programs' temporaries."""
    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations import mimo_v2 as mm
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    monkeypatch.setattr(gm, "_should_interpret", lambda: False)
    g = MIMO
    one = SingleDeviceSharding(chips[0])
    pattern = (0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0)
    cfg = mm.MiMoV2Config(
        vocab_size=19072, num_hidden_layers=12,
        hybrid_layer_pattern=pattern, moe_layer_freq=(0,) + (1,) * 11,
        experts_held=(0, 8))
    assert (cfg.kv_heads, cfg.ring_kv_heads, cfg.head_dim, cfg.v_head_dim,
            cfg.sliding_window) == (4, 8, 192, 128, g["window"])
    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: mm.init_params(jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_paged_cache(
        cfg.n_layer, g["slots"], g["blocks"], BS, g["span"], cfg.kv_heads,
        cfg.head_dim, BF16, window_layers=cfg.window_layers,
        window=cfg.sliding_window, aux_shape=cfg.aux_shape,
        v_head_dim=cfg.v_head_dim, ring_kv_heads=cfg.ring_kv_heads)))
    assert cache.k.shape == (3, g["blocks"], BS, 4 * 192)
    assert cache.v.shape == (3, g["blocks"], BS, 4 * 128)
    assert cache.ring_k.shape == (9, g["slots"] * 2, BS, 8 * 192)
    assert cache.ring_v.shape == (9, g["slots"] * 2, BS, 8 * 128)
    fn, name, args = {
        "decode": (Srv._decode_fn, "serve_decode",
                   (params, arr((g["slots"],)), cache,
                    arr((g["slots"],), jnp.bool_))),
        "prefill": (Srv._prefill_fn, "serve_prefill",
                    (params, arr((1, g["prompt"])), arr((1,)), cache,
                     arr(()))),
    }[kind]
    da._paged_call.cache_clear()
    compiled = jax.jit(compile_watch._named(
        functools.partial(fn, cfg=cfg, mesh=None), name),
        donate_argnames=("cache",)).lower(*args).compile()
    text = compiled.as_text()
    assert f"HloModule jit_{name}" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    ours = _without_grouped_matmuls(kernels, scopes)
    # w_in and w_out a layer, in each branch of held_experts_part's
    # ``cond`` (the expected rows, and the exact ``T k`` fallback)
    assert len(kernels) - len(ours) == 4 * 11
    counts = {v: list(ours.values()).count(v) for v in set(ours.values())}
    assert counts == kernels_want
    for k, v in ours.items():
        assert scopes[k].split("/")[0] == (
            "attn_window" if "window" in v else "attn_full"), (k, scopes[k])
    words = {w for v in scopes.values() if v for w in v.split("/")}
    assert words >= MIMO_SCOPES, MIMO_SCOPES - words
    assert "kv_read" not in words and "moe_shared" not in words
    stores = (cache.k.shape, cache.v.shape, cache.ring_k.shape,
              cache.ring_v.shape)
    smallest = min(math.prod(s[1:]) * 2 for s in stores)    # a layer of V
    rows = {s[-2:] for s in stores}
    assert not _copies(
        text, lambda dims, nbytes: dims not in stores and nbytes >= smallest
        and dims[-2:] in rows, kernels=kernels)
    assert not [c for c in _copies(
        text, lambda dims, nbytes: dims in stores, kernels=kernels)
        if not any(op in c for op in _IN_PLACE)]
    mem = compiled.memory_analysis()
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    pool = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(cache))
    print(f"mimo {kind}: weights {weights / 1e9:.3f} GB, cache "
          f"{pool / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    # benchmark/configs/mimo-v2-flash-ep32-serve.json "why": 7.40 GB of
    # weights, 6.46 GB of cache
    assert 7.35e9 < weights < 7.45e9 and 6.4e9 < pool < 6.5e9
    if kind == "decode":
        assert mem.temp_size_in_bytes < smallest
    else:
        assert mem.temp_size_in_bytes < 2.5e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16.9e9)


GRANITE_SCOPES = {"embed", "ln", "mamba_in", "mamba_conv", "mamba_out",
                  "attn_full", "kv_write", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_shared", "lm_head",
                  "sample"}


@pytest.mark.parametrize("kind,kernel,scope", [
    ("decode", "paged_decode_attention", "mamba_state"),
    ("prefill", "flash_attention_fwd", "mamba_scan")])
def test_state_layers_update_in_place_beside_the_block_pool(
        chips, monkeypatch, kind, kernel, scope):
    """The Granite hybrid's two serving programs at the published widths
    and the cell's slots, pool and span (two Mamba layers around the
    attention layer, two held experts and a small vocabulary), read back
    from their compiled text: module, kernel and scope names; ONE kernel
    call for the one attention layer, under ``attn_full``; no ``kv_read``
    (nothing gathers the pool); and no instruction but an in-place write
    makes anything shaped like a state layer's buffer or as large: the
    decode update and the prefill's write of one slot happen in the
    donated pool, and the decode program's temporaries stay under a
    tenth of ONE state buffer."""
    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations import granite_hybrid as gh
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    one = SingleDeviceSharding(chips[0])
    slots, blocks, span, prompt = 96, 1 + 2400, 48, 1024
    cfg = gh.GraniteHybridConfig(
        vocab_size=2048, num_hidden_layers=3,
        layer_types=(gh.MAMBA, gh.ATTENTION, gh.MAMBA), experts_held=(0, 2))
    assert cfg.state_shapes == ((128, 64, 128), (3, 8448))
    assert (cfg.n_head, cfg.kv_heads, cfg.head_dim) == (32, 8, 128)
    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: gh.init_params(jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_paged_cache(
        cfg.n_layer, slots, blocks, BS, span, cfg.kv_heads, cfg.head_dim,
        BF16, aux_shape=cfg.aux_shape, state_layers=cfg.state_layers,
        state_shapes=cfg.state_shapes, state_dtype=cfg.state_dtype)))
    assert cache.k.shape == (1, blocks, BS, 1024)
    assert [a.shape for a in cache.state] == [(slots, 128, 64, 128)] * 2
    assert [a.shape for a in cache.conv] == [(3, slots, 8448)] * 2
    fn, name, args = {
        "decode": (Srv._decode_fn, "serve_decode",
                   (params, arr((slots,)), cache, arr((slots,), jnp.bool_))),
        "prefill": (Srv._prefill_fn, "serve_prefill",
                    (params, arr((1, prompt)), arr((1,)), cache, arr(()))),
    }[kind]
    da._paged_call.cache_clear()
    compiled = jax.jit(compile_watch._named(
        functools.partial(fn, cfg=cfg, mesh=None), name),
        donate_argnames=("cache",)).lower(*args).compile()
    text = compiled.as_text()
    assert f"HloModule jit_{name}" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    ours = _without_grouped_matmuls(kernels, scopes)
    assert list(ours.values()) == [kernel]
    assert all(scopes[k].split("/")[0] == "attn_full" for k in ours)
    words = {w for v in scopes.values() if v for w in v.split("/")}
    assert words >= GRANITE_SCOPES | {scope}, (GRANITE_SCOPES | {scope}
                                               ) - words
    assert "kv_read" not in words
    state = cache.state[0]
    state_bytes = math.prod(state.shape) * 4
    # (a convolution tail, 5 MB a layer, is shifted whole every step)
    stores = (state.shape, cache.k.shape)
    # the instructions that run, not the bodies of their fusions (whose
    # intermediates never reach memory)
    entry = text[text.index("\nENTRY "):]
    assert not [c for c in _copies(
        entry, lambda dims, nbytes: dims in stores or nbytes >= state_bytes,
        kernels=kernels) if not any(op in c for op in _IN_PLACE)]
    # every state layer's buffer comes back as the buffer it came in
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in cache.state + cache.conv + (cache.k, cache.v))
    if kind == "decode":
        assert compiled.memory_analysis().temp_size_in_bytes < \
            state_bytes // 10


@pytest.mark.parametrize("kind,kernel,scope", [
    ("decode", "paged_decode_attention", "mamba_state"),
    ("prefill", "flash_attention_fwd", "mamba_scan")])
def test_one_mixer_a_layer_serves_without_a_copy_of_a_weight_or_a_state(
        chips, monkeypatch, kind, kernel, scope):
    """The Nemotron-H family's two serving programs at the published
    widths and the cell's slots, pool and span (pattern ``ME*EM``, 16
    held experts, too many for the compiler to park a layer's weights in
    VMEM ahead of the call, and a small vocabulary), read back from their
    compiled text: ONE kernel call for the one attention layer; the pool holds
    that ONE layer's rows (the expert layers keep nothing); the state
    update over 8 B/C groups happens in the donated pool; and NO
    instruction copies an expert's weights: stored 1920 wide they are
    row-major as the chip lays them out (stored 1856 wide the chip's own
    layout puts the 2688 minor and every call copied ``w_in``)."""
    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations import nemotron_h as nh
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    monkeypatch.setattr(gm, "_should_interpret", lambda: False)
    one = SingleDeviceSharding(chips[0])
    slots, blocks, span, prompt = 256, 1 + 2048, 80, 1024
    cfg = nh.NemotronHConfig(vocab_size=2048, hybrid_override_pattern="ME*EM",
                             num_hidden_layers=5, experts_held=(0, 16))
    assert cfg.state_shapes == ((64, 64, 128), (3, 6144))
    assert (cfg.n_head, cfg.kv_heads, cfg.head_dim) == (32, 2, 128)
    abstract = functools.partial(_abstract, sharding=one)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: nh.init_params(jax.random.PRNGKey(0), cfg)))
    experts = params["layers"][1]["moe"]["experts"]
    assert experts["w_in"].shape == (16, 2688, 1920)
    cache = abstract(jax.eval_shape(lambda: init_paged_cache(
        cfg.n_layer, slots, blocks, BS, span, cfg.kv_heads, cfg.head_dim,
        BF16, aux_shape=cfg.aux_shape, state_layers=cfg.state_layers,
        state_shapes=cfg.state_shapes, state_dtype=cfg.state_dtype,
        cacheless_layers=cfg.cacheless_layers)))
    assert cache.k.shape == (1, blocks, BS, 256)
    assert [a.shape for a in cache.state] == [(slots, 64, 64, 128)] * 2
    fn, name, args = {
        "decode": (Srv._decode_fn, "serve_decode",
                   (params, arr((slots,)), cache, arr((slots,), jnp.bool_))),
        "prefill": (Srv._prefill_fn, "serve_prefill",
                    (params, arr((1, prompt)), arr((1,)), cache, arr(()))),
    }[kind]
    da._paged_call.cache_clear()
    compiled = jax.jit(compile_watch._named(
        functools.partial(fn, cfg=cfg, mesh=None), name),
        donate_argnames=("cache",)).lower(*args).compile()
    text = compiled.as_text()
    scopes, kernels = compile_watch.parse_scopes(text)
    assert list(kernels.values()).count(gm.NAME) >= 4    # two layers x two
    ours = _without_grouped_matmuls(kernels, scopes)
    assert list(ours.values()) == [kernel]
    words = {w for v in scopes.values() if v for w in v.split("/")}
    assert words >= GRANITE_SCOPES | {scope}
    state_bytes = math.prod(cache.state[0].shape) * 4
    weight_bytes = math.prod(experts["w_in"].shape) * 2
    stores = (cache.state[0].shape, cache.k.shape,
              experts["w_in"].shape, experts["w_out"].shape)
    entry = text[text.index("\nENTRY "):]
    assert not [c for c in _copies(
        entry, lambda dims, nbytes: dims in stores or nbytes >= weight_bytes,
        kernels=kernels) if not any(op in c for op in _IN_PLACE)]
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in cache.state + cache.conv + (cache.k, cache.v))
    if kind == "decode":
        assert compiled.memory_analysis().temp_size_in_bytes < \
            state_bytes // 10


def test_train_model_kernels_and_scopes(chips, monkeypatch):
    """The train step's model under the step's ``fwd_bwd`` scope: the
    two flash kernels by name, under a gradient as in the forward."""
    from deepspeed_tpu.models.gpt2 import GPT2LMModel, config_for
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    one = SingleDeviceSharding(chips[0])
    tm = GPT2LMModel(config_for("gpt2-125m", dtype=BF16, n_embd=256,
                                n_layer=2, n_head=2, vocab_size=512,
                                n_positions=256))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(lambda: tm.init(jax.random.PRNGKey(0), batch_size=1,
                                       seq_len=256)))
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 256), jnp.int32,
                                               sharding=one)}

    def train_step(p, b):
        with jax.named_scope("fwd_bwd"):
            return jax.value_and_grad(lambda q: tm.loss_fn(q, b))(p)
    text = jax.jit(train_step).lower(params, batch).compile().as_text()
    assert "HloModule jit_train_step" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    assert set(kernels.values()) == {
        "flash_attention_fwd", "flash_attention_bwd"}
    assert all(scopes[k] == "fwd_bwd/attn_kernel" for k in kernels)
    paths = {v for v in scopes.values() if v}
    assert all(v.split("/")[0] == "fwd_bwd" for v in paths)
    assert {v.rsplit("/", 1)[-1] for v in paths} >= {
        "fwd_bwd", "embed", "attn_kernel", "mlp", "lm_head"}


def test_streamed_optimizer_state_moves_once_each_way(chips):
    """The offload stream as ``engine._make_step_fn`` writes it: master
    and moments enter in ``pinned_host``, each leaf goes to the device
    (``device_put`` to the same sharding's ``device`` kind) for the
    update and back after, under the ``optimizer`` scope. The movement
    table of the program compiled for the chip reads every leaf once in
    each direction, by its bytes, and nothing between chips."""
    from deepspeed_tpu.telemetry import compile_watch
    mesh = Mesh(np.asarray(chips[:1]), ("fsdp",))
    host = NamedSharding(mesh, P(), memory_kind="pinned_host")
    dev = host.with_memory_kind("device")
    shapes = {"w": (512, 256), "b": (256,)}
    tree = lambda sh: {k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=sh)  # noqa: E731
                       for k, v in shapes.items()}
    state = {"master": tree(host), "mu": tree(host), "nu": tree(host)}
    grads = tree(dev)

    def train_step(state, grads):
        with jax.named_scope("optimizer"):
            on_dev = jax.tree.map(lambda x: jax.device_put(x, dev), state)
            mu = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g,
                              on_dev["mu"], grads)
            nu = jax.tree.map(lambda v, g: 0.99 * v + 0.01 * g * g,
                              on_dev["nu"], grads)
            master = jax.tree.map(
                lambda p, m, v: p - 1e-3 * m / (jnp.sqrt(v) + 1e-8),
                on_dev["master"], mu, nu)
            new = {"master": master, "mu": mu, "nu": nu}
            params = jax.tree.map(lambda p: p.astype(BF16), master)
            return jax.tree.map(lambda x: jax.device_put(x, host),
                                new), params
    text = jax.jit(train_step, donate_argnums=(0,),
                   out_shardings=(jax.tree.map(lambda _: host, state),
                                  {k: dev for k in shapes})
                   ).lower(state, grads).compile().as_text()
    table = compile_watch.parse(text).movement
    state_bytes = 3 * sum(int(np.prod(v)) * 4 for v in shapes.values())
    moved = {"host_to_device": 0, "device_to_host": 0}
    for name, row in table.items():
        assert row["kind"] in moved, (name, row)     # no collective row
        assert row["pair"] in table and table[row["pair"]]["pair"] == name
        assert row["pass"] == "optimizer" and row["scopes"] == "optimizer"
        if row["role"] == "start":
            moved[row["kind"]] += row["bytes"]
    assert moved == {"host_to_device": state_bytes,
                     "device_to_host": state_bytes}
    per_step = compile_watch.movement_per_step(table)
    assert per_step["host_to_device"] == {"bytes": state_bytes, "calls": 6}
    assert per_step["device_to_host"] == {"bytes": state_bytes, "calls": 6}


def _aot_script():
    """``scripts/aot_train_step.py`` as a module (its ``entry_order`` /
    ``stream_order`` read a compiled text's ENTRY order)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "aot_train_step.py")
    spec = importlib.util.spec_from_file_location("aot_train_step", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("fsdp", [1, 4], ids=["unsharded", "fsdp4"])
def test_stream_pipeline_keeps_both_directions_in_flight(chips, fsdp):
    """The stream's update as the ENGINE calls it
    (``offload_stream.streamed_update``, with the compiler option the
    engine sets), compiled for the chip over ten leaf triples of
    unequal size: (i) every leaf moves once each way by its bytes and
    nothing else crosses the link; (ii) every stage's store but the
    first's and the last's has a fetch in flight beside it; (iii) the
    copies outstanding at any line fill, and never pass, the budget of
    ``LAG + 1 + AHEAD`` stages (one stage more may show half begun).
    With ``fsdp=4`` each chip moves its own quarter of every leaf."""
    from deepspeed_tpu.ops.adam import adam
    from deepspeed_tpu.runtime.zero import offload_stream as osm
    from deepspeed_tpu.telemetry import compile_watch
    mesh = Mesh(np.asarray(chips[:fsdp]), ("fsdp",))
    spec = P("fsdp") if fsdp > 1 else P()
    host = NamedSharding(mesh, spec, memory_kind="pinned_host")
    dev = host.with_memory_kind("device")
    rows = [8, 16, 24, 32, 48, 64, 96, 128, 192, 256]   # x 512 float32
    shapes = {f"w{i}": (r * 8, 512) for i, r in enumerate(rows)}
    tree = lambda sh: {k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=sh)  # noqa: E731
                       for k, v in shapes.items()}
    opt = adam(weight_decay=0.01)
    opt_abs = jax.eval_shape(opt.init, tree(dev))
    count_sh = NamedSharding(mesh, P(), memory_kind="pinned_host")
    opt_sh = opt_abs.replace(count=count_sh, mu={k: host for k in shapes},
                             nu={k: host for k in shapes})
    opt_state = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        opt_abs, opt_sh)
    master_sh = {k: host for k in shapes}
    budget = osm.copy_budget(opt_abs, True)     # as engine._compile_step
    assert budget == 3 * (osm.LAG + 1 + osm.AHEAD)

    def train_step(master, opt_state, grads):
        with jax.named_scope("optimizer"):
            new_master, new_opt, params, _ = osm.streamed_update(
                opt, grads, master, opt_state, jnp.float32(1e-3),
                master_sh=master_sh, opt_sh=opt_sh, compute_dtype=BF16)
        return new_master, new_opt, params
    text = jax.jit(
        train_step, donate_argnums=(0, 1),
        out_shardings=(master_sh, opt_sh, {k: dev for k in shapes}),
        compiler_options={osm.COPY_BUDGET_OPTION: budget},
    ).lower(tree(host), opt_state, tree(dev)).compile().as_text()
    table = compile_watch.parse(text).movement
    # (i) what moves: each leaf's shard three times each way (+ the
    # step counter's four bytes), all of it the optimizer's
    leaf_bytes = {k: int(np.prod(v)) * 4 // fsdp for k, v in shapes.items()}
    assert len(set(leaf_bytes.values())) == len(shapes)
    moved = {"host_to_device": [], "device_to_host": []}
    for name, row in table.items():
        assert row["kind"] in moved, (name, row)     # no collective row
        assert row["pair"] in table and table[row["pair"]]["pair"] == name
        assert row["pass"] == "optimizer" and row["scopes"] == "optimizer"
        assert not row["per_iteration"]              # in the ENTRY
        if row["role"] == "start":
            moved[row["kind"]].append(int(row["bytes"]))
    want = sorted(3 * list(leaf_bytes.values()))
    for kind, sizes in moved.items():
        assert sorted(b for b in sizes if b > 4) == want, kind
        assert len(sizes) - len(want) <= 1           # the counter
    # (ii), (iii): the ENTRY's order. A stage is a leaf: its bytes name it
    events, _, length = _aot_script().entry_order(text, table)
    span = {}                   # (kind, bytes) -> [first start, last done]
    copies = np.zeros(length + 2, int)      # outstanding at each position
    for pos, _, row in events:
        if row["bytes"] <= 4:
            continue
        copies[pos:] += 1 if row["role"] == "start" else -1
        key = row["kind"], row["bytes"]
        span[key] = (min(span.get(key, (pos,))[0], pos), pos)
    fetches = [v for (kind, _), v in span.items()
               if kind == "host_to_device"]
    stores = sorted(v for (kind, _), v in span.items()
                    if kind == "device_to_host")
    assert len(fetches) == len(stores) == len(shapes)
    for lo, hi in stores[1:-1]:
        assert any(f_lo < hi and lo < f_hi for f_lo, f_hi in fetches), \
            (lo, hi, sorted(fetches))
    # the compiler's budget is in copies, three a stage, and the pipeline
    # fills it; a stage half begun or half ended at a line still counts,
    # so one more stage than the budget's worth may show
    assert 3 * osm.LAG < copies.max() <= budget
    most = max(len({b for (_, b), (lo, hi) in span.items() if lo <= pos < hi})
               for pos in range(1, length + 1))
    assert most <= osm.LAG + 1 + osm.AHEAD + 1, most


def test_zero3_step_exchanges_parameters_not_activations(chips):
    """The x4 cell's OWN step (``deepspeed_tpu.initialize``, ZeRO-3 over
    ``fsdp=4``, GPT-2 1.3B widths) at two layers: with the model's
    residual-stream constraint live inside the engine's trace
    (``utils/sharding.maybe_constrain``) the partitioner gathers weights
    and scatters gradients and leaves activations where they are. While
    the constraint was dead it ran the projections like tensor
    parallelism: 18 all-to-alls and 4.7 GB at this depth, 171 and 30.5 GB
    at 24 layers (PERF.md section 6, PR 44)."""
    from deepspeed_tpu.telemetry import compile_watch
    step = _aot_script().compile_step(traffic="zero3-x4", layers=2)
    assert step["chips"] == 4
    table = compile_watch.parse(step["text"]).movement
    moved = compile_watch.movement_per_step(table)
    # the embedding's lookup and its scatter-add, whatever the depth
    assert moved["all-to-all"]["calls"] <= 2, moved
    # a chip receives three quarters of every bf16 parameter twice
    # (forward, backward) and sends three quarters of its gradient once
    zero3 = 3 * 2 * step["parameters"] * 3 / 4
    wire = moved["all-gather"]["bytes"] + moved["reduce-scatter"]["bytes"]
    assert 0.5 * zero3 < wire < 1.2 * zero3, (wire, zero3, moved)


def test_offload_step_report_states_its_footprint_and_its_clones(chips):
    """The offload cell's OWN step at two layers through
    ``scripts/aot_train_step.py``: the report reads the planned footprint
    (``memory_analysis()``) against the v5e's stated limit and counts the
    compiler's own rematerialisation of matmuls, so that the next reader
    needs no chip for either (PERF.md section 6, PR 48: at 24 layers the
    parent's step held 99 such clones, at a limit of 0 bytes)."""
    script = _aot_script()
    step = script.compile_step(traffic="offload", layers=2)
    memory = step["memory"]
    assert set(memory) == {"argument_bytes", "temp_bytes", "planned_bytes",
                           "bytes_limit_v5e"}
    # bf16 parameters are the arguments on the chip; the state on the
    # host (12 bytes a parameter) is none of them
    assert memory["argument_bytes"] == pytest.approx(
        2 * step["parameters"], rel=0.01)
    assert memory["planned_bytes"] == \
        memory["argument_bytes"] + memory["temp_bytes"]
    assert 0 < memory["planned_bytes"] < memory["bytes_limit_v5e"]
    assert script.remat_clones(step["text"]) == {"count": 0,
                                                 "by_product": {}}
    # what the counter counts: a clone whose fusion holds a matmul
    text = """
%fused_computation.7 (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  ROOT %convolution.1 = bf16[8,8]{1,0} convolution(%p, %p), dim_labels=bf_io->bf
}
%fused_computation.8 (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  ROOT %add.1 = bf16[8,8]{1,0} add(%p, %p)
}
ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %fusion.3.remat2 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(train_step)/fwd_bwd/jvp(GPT2)/h_3/mlp/c_fc/dot_general"}
  ROOT %fusion.4.remat = bf16[8,8]{1,0} fusion(%fusion.3.remat2), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(train_step)/fwd_bwd/jvp(GPT2)/h_3/mlp/add"}
}
"""
    assert script.remat_clones(text) == {
        "count": 1,
        "by_product": {"mlp/c_fc/dot_general -> bf16[8,8]{1,0}": 1}}


def test_offload_step_is_not_rematerialised_for_its_host_state(chips):
    """The offload cell's step at 16 layers, the shallowest at which the
    compiler's rematerialisation pass, taking the optimizer state in
    pinned host memory off its limit with the other outputs, ran out of
    room on the parent (67 ``.remat`` clones of matmuls there, 99 at 24
    layers; none at 12): with the host's share given back
    (``engine._remat_limit_percent``) it clones nothing."""
    script = _aot_script()
    step = script.compile_step(traffic="offload", layers=16)
    assert script.remat_clones(step["text"]) == {"count": 0,
                                                 "by_product": {}}
    assert step["memory"]["planned_bytes"] < step["memory"]["bytes_limit_v5e"]


def test_this_libtpu_knows_the_remat_limit_option(chips):
    """The option the engine sets on every step whose state lives on the
    host is an internal one of the TPU compiler: a libtpu that drops it
    would fail every such step at compile time, as it fails an option it
    does not know, by name. Verified with libtpu 0.0.34."""
    from deepspeed_tpu.runtime.activation_checkpointing import (
        REMAT_LIMIT_OPTION)
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=NamedSharding(
        Mesh(np.array(chips[:1]), ("x",)), P()))

    def compile_with(option):
        return jax.jit(lambda a: a * 2, compiler_options={
            option: 188}).lower(x).compile()

    assert compile_with(REMAT_LIMIT_OPTION) is not None
    with pytest.raises(Exception, match="xla_jf_no_such_option"):
        compile_with("xla_jf_no_such_option")


# sha256[:16] and line count of ``jax.make_jaxpr`` text (the kernel
# bodies are in it), read off the tree at 1f90ef5 (PR 52) by the same
# function: PR 53 gave the decode kernel a value width, a sink and a
# rule for heads of 192 lanes, and the flash forward a value width and a
# sink; with ``D_v == D`` and no sink every accepted program traces to
# the text it did. A PR that MEANS to change one of these programs
# replaces its line here and says so in CHANGES.md: PR 57 replaced
# ``laguna-decode`` (c2da6142f4089a74, 9654 lines: its two one-token
# walks attend two table entries an iteration, a product a head, so a
# kernel body holds a whole group and a group of one; the three
# ``gpt2-*`` programs walk an entry an iteration, as they did); PR 59
# replaced both ``laguna-*`` lines with the ones below (they were
# 90e462daa2cb3975, 21558 lines and 27010f6634f018c7, 7922 lines: the
# expert layers lay their groups out on row-tile boundaries, the grouped
# matmul's kernel is a dot and a store, a decode batch is dispatched by a
# one-hot product, the combine's product takes the weights in three
# bfloat16 parts and the routing row gained ``row_tiles_walked``; the
# ``gpt2-*`` programs hold no expert layer and trace to the text they
# did).
ACCEPTED_PROGRAMS = {
    "laguna-decode": ("f359b8214c08e792", 21257, 9),
    "laguna-prefill": ("0637feab3876d315", 7928, 8),
    "gpt2-decode": ("3141ca124377a618", 8987, 24),
    "gpt2-decode-int8": ("3bf89725c010ea29", 12107, 24),
    "gpt2-verify": ("c4007aa33affbfb2", 11630, 24),
}


@pytest.mark.parametrize("case", sorted(ACCEPTED_PROGRAMS))
def test_accepted_decode_programs_trace_to_the_text_they_did(monkeypatch,
                                                             case):
    """Laguna's two programs at its AOT test's geometry and GPT-2 1.3B's
    decode / verify programs at the batch cell's (24 layers, 32 slots,
    257 blocks, 16 heads x 128; fp and int8 pools), traced for the TPU
    path: the text's hash, its lines and its kernel calls."""
    import hashlib

    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    monkeypatch.setattr(gm, "_should_interpret", lambda: False)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)
    if case.startswith("laguna"):
        from deepspeed_tpu.model_implementations import laguna as lg
        cfg = lg.LagunaConfig(
            vocab_size=2048, num_hidden_layers=5,
            layer_types=(lg.FULL,) + (lg.WINDOW,) * 3 + (lg.FULL,),
            mlp_layer_types=("dense",) + ("sparse",) * 4,
            num_attention_heads_per_layer=(48, 64, 64, 64, 48),
            rope_full=lg.RopeSpec(
                rope_theta=500000, rope_type="yarn", factor=64,
                original_max_position_embeddings=4096, beta_fast=64,
                partial_rotary_factor=0.5),
            rope_sliding=lg.RopeSpec(rope_theta=10000), experts_held=(0, 2))
        params = jax.eval_shape(
            lambda: lg.init_params(jax.random.PRNGKey(0), cfg))
        cache = jax.eval_shape(lambda: init_paged_cache(
            cfg.n_layer, 96, 3201, BS, 80, cfg.kv_heads, cfg.head_dim, BF16,
            window_layers=cfg.window_layers, window=cfg.sliding_window,
            aux_shape=cfg.aux_shape))
        slots = 96
    else:
        from deepspeed_tpu.model_implementations.transformer import (
            InferenceTransformerConfig, init_params)
        cfg = InferenceTransformerConfig(
            vocab_size=50304, n_positions=1024, n_embd=2048, n_layer=24,
            n_head=16, dtype=BF16)
        params = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        cache = jax.eval_shape(lambda: init_paged_cache(
            24, 32, 257, BS, 8, 16, 128, BF16,
            quantized=case.endswith("int8")))
        slots = 32
    fn, args = {
        "decode": (Srv._decode_fn, (params, arr((slots,)), cache,
                                    arr((slots,), jnp.bool_))),
        "prefill": (Srv._prefill_fn, (params, arr((1, 8192)), arr((1,)),
                                      cache, arr(()))),
        "verify": (Srv._verify_fn, (params, arr((slots, 4)), cache)),
    }[case.split("-")[1]]
    da._paged_call.cache_clear()
    text = str(jax.make_jaxpr(functools.partial(fn, cfg=cfg, mesh=None))(
        *args))
    assert (hashlib.sha256(text.encode()).hexdigest()[:16],
            text.count("\n"), text.count("pallas_call[")
            ) == ACCEPTED_PROGRAMS[case]


def test_kernel_names_are_the_same_under_a_mesh(chips):
    """``map_kernel``'s shard_map does not rename the call: the decode
    kernel reads ``paged_decode_attention`` on four devices as on one."""
    from deepspeed_tpu.telemetry import compile_watch
    from deepspeed_tpu.utils.sharding import map_kernel
    mesh = Mesh(np.asarray(chips).reshape(1, 1, 4),
                ("expert", "seq", "tensor"))
    fn, shapes = _paged_decode()
    hs = "tensor"
    specs = (P(None, hs, None), P(None, None, None, hs),
             P(None, None, None, hs), P(), P())
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for (shape, dtype), spec in zip(shapes, specs)]
    mapped = map_kernel(fn, mesh, specs, specs[0])
    text = jax.jit(mapped).lower(*args).compile().as_text()
    _, kernels = compile_watch.parse_scopes(text)
    assert set(kernels.values()) == {"paged_decode_attention"}
