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
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas import layer_norm as ln

# GPT-2 1.3B serving geometry: 16 heads x D=128, block 128, 8 slots,
# 1024-token context, 4-token speculation window, 256-token chunk
S, MB, BS, NB, K, C = 8, 8, 128, 65, 4, 256
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
    operands as shapes: q, k pool, v pool, table, bound[, k_scale,
    v_scale]. ``q_shape`` is given without its trailing ``(H, D)``."""
    kernel = getattr(da, f"paged_{kind}_attention")
    pool = ((NB, BS, KH, D), jnp.int8 if int8 else BF16)
    scales = [((NB, KH, BS), jnp.float32)] * 2 if int8 else []

    def fn(q, k, v, table, bound, *sc):
        return kernel(q, k, v, table, bound, interpret=False,
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


def _flash(grad):
    qkv = ((2, 1024, 16, 128), BF16)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()
    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd), [qkv] * 3


def _layer_norm():
    def loss(x, w, b):
        return ln.fused_layer_norm(x, w, b).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), [
        ((2048, 2048), BF16), ((2048,), jnp.float32),
        ((2048,), jnp.float32)]


CASES = {
    "decode": _dense_decode,
    "paged_decode-fp": _paged_decode,
    "paged_decode-int8": functools.partial(_paged_decode, int8=True),
    "paged_chunk-fp": _paged_chunk,
    "paged_chunk-int8": functools.partial(_paged_chunk, int8=True),
    "paged_verify-fp": _paged_verify,
    "paged_verify-int8": functools.partial(_paged_verify, int8=True),
    "paged_decode-gqa-kh4": functools.partial(_paged_decode, KH=4),
    "paged_decode-d64-int8": functools.partial(_paged_decode, D=64,
                                               int8=True),
    "flash-fwd": functools.partial(_flash, grad=False),
    "flash-fwd-bwd": functools.partial(_flash, grad=True),
    "layer_norm-fwd-bwd": _layer_norm,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, chips, monkeypatch):
    # flash / layer-norm read the backend to pick interpret mode; the
    # process is on the CPU, the compile target is not
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    monkeypatch.setattr(ln, "_should_interpret", lambda: False)
    fn, shapes = CASES[case]()
    one = SingleDeviceSharding(chips[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_maps_over_a_mesh(chips):
    """GSPMD cannot partition a Mosaic call ("wrap the call in a
    shard_map"): on four devices the paged decode kernel goes through
    ``map_kernel`` over the kv-head axis, as tensor-parallel serving
    lays the pool out — and the compiler must not have gathered the
    pool to make that work."""
    from deepspeed_tpu.utils.sharding import map_kernel
    mesh = Mesh(np.asarray(chips).reshape(1, 1, 4),
                ("expert", "seq", "tensor"))
    fn, shapes = _paged_decode()
    q, pool = P(None, "tensor", None), P(None, None, "tensor", None)
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


def _serve_program(kind, device):
    """``(jitted program named as the server names it, its name,
    abstract arguments)`` at d_head 128, block 128, 8 slots."""
    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.inference.server import ContinuousBatchingServer as Srv
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, init_params)
    from deepspeed_tpu.telemetry import compile_watch
    one = SingleDeviceSharding(device)
    cfg = InferenceTransformerConfig(
        vocab_size=512, n_positions=1024, n_embd=256, n_layer=L_NAMES,
        n_head=2, dtype=BF16)

    def abstract(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = abstract(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: init_paged_cache(
        L_NAMES, S, NB, BS, MB, 2, 128, BF16)))
    fn, name, args = {
        "decode": (Srv._decode_fn, "serve_decode",
                   (params, arr((S,)), cache, arr((S,), jnp.bool_))),
        "prefill": (Srv._prefill_fn, "serve_prefill",
                    (params, arr((1, C)), arr((1,)), cache, arr(()))),
        "chunk": (Srv._chunk_fn, "serve_prefill_chunk",
                  (params, arr((1, C)), arr(()), arr((1,)), cache,
                   arr(()))),
        "verify": (Srv._verify_fn, "serve_spec_verify",
                   (params, arr((S, K)), cache)),
    }[kind]
    prog = jax.jit(compile_watch._named(
        functools.partial(fn, cfg=cfg, mesh=None), name),
        donate_argnames=("cache",))
    return prog, name, args


@pytest.mark.parametrize("kind,kernel,extra", [
    ("decode", "paged_decode_attention", {"kv_read"}),
    ("prefill", "flash_attention_fwd", set()),
    ("chunk", "paged_chunk_attention", {"kv_read"}),
    ("verify", "paged_verify_attention", {"kv_read"}),
])
def test_serving_programs_carry_their_names(chips, monkeypatch, kind,
                                            kernel, extra):
    """Module name, kernel name and every layer scope of a serving
    program, read back from its compiled text."""
    from deepspeed_tpu.telemetry import compile_watch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_should_interpret", lambda: False)
    prog, name, args = _serve_program(kind, chips[0])
    text = prog.lower(*args).compile().as_text()
    assert f"HloModule jit_{name}" in text
    scopes, kernels = compile_watch.parse_scopes(text)
    assert set(kernels.values()) == {kernel}
    assert len(kernels) == L_NAMES            # one call a layer
    assert all(scopes[k] == "attn_kernel" for k in kernels)
    innermost = {v.rsplit("/", 1)[-1] for v in scopes.values() if v}
    assert innermost >= SERVE_SCOPES | extra, SERVE_SCOPES - innermost
    if "kv_read" in extra:
        # the per-layer cut of K and V out of the pool is kv_read's: a
        # slice of the whole pool and the squeeze the compiler merges
        # with the kernel's input reshape
        cuts = [k for k, v in scopes.items() if v == "kv_read"
                and k.split(".")[0] in ("slice", "squeeze")]
        assert len(cuts) >= 2 * L_NAMES


def test_train_model_kernels_and_scopes(chips, monkeypatch):
    """The train step's model under the step's ``fwd_bwd`` scope: the
    three flash kernels by name, under a gradient as in the forward."""
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
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv"}
    assert all(scopes[k] == "fwd_bwd/attn_kernel" for k in kernels)
    paths = {v for v in scopes.values() if v}
    assert all(v.split("/")[0] == "fwd_bwd" for v in paths)
    assert {v.rsplit("/", 1)[-1] for v in paths} >= {
        "fwd_bwd", "embed", "attn_kernel", "mlp", "lm_head"}


def test_kernel_names_are_the_same_under_a_mesh(chips):
    """``map_kernel``'s shard_map does not rename the call: the decode
    kernel reads ``paged_decode_attention`` on four devices as on one."""
    from deepspeed_tpu.telemetry import compile_watch
    from deepspeed_tpu.utils.sharding import map_kernel
    mesh = Mesh(np.asarray(chips).reshape(1, 1, 4),
                ("expert", "seq", "tensor"))
    fn, shapes = _paged_decode()
    hs = "tensor"
    specs = (P(None, hs, None), P(None, None, hs, None),
             P(None, None, hs, None), P(), P())
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for (shape, dtype), spec in zip(shapes, specs)]
    mapped = map_kernel(fn, mesh, specs, specs[0])
    text = jax.jit(mapped).lower(*args).compile().as_text()
    _, kernels = compile_watch.parse_scopes(text)
    assert set(kernels.values()) == {"paged_decode_attention"}
