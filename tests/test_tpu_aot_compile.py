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
