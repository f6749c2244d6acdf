"""Kernel/op tests — numerical parity against jnp oracles (the reference's
tests/unit/ops strategy: each op vs a torch/numpy reference)."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import causal_attention_reference
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention, decode_attention_reference)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.layer_norm import (fused_layer_norm,
                                                 fused_residual_layer_norm,
                                                 layer_norm_reference)
from deepspeed_tpu.ops.quantizer import (Quantizer, dequantize_asymmetric,
                                         dequantize_symmetric, fake_quantize,
                                         quantize_asymmetric,
                                         quantize_symmetric)
from deepspeed_tpu.ops import random_ltd


def _masked_softmax_attention(q, k, v, causal):
    """The oracle of both masks, k/v expanded over the query group."""
    rep = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
    if causal:
        return causal_attention_reference(q, k, v)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), v)


def _kernel_names(fn, *args):
    """The Pallas kernels a traced function calls, by name."""
    return set(re.findall(r"name=(flash_attention\w*)",
                          str(jax.make_jaxpr(fn)(*args))))


# T, D, H, HKV, causal, block_q, block_k, dtype: the cells' shapes (T 1024,
# D 128), the latent prefill's (D 192), Laguna's group of query heads,
# every D on both masks, unequal blocks both ways, blocks that fall back
# to 128 (T 384), one block a head (T 128) and eight
_FLASH_CASES = [
    (256, 64, 4, 4, True, 256, 256, "float32"),
    (128, 64, 4, 4, False, 256, 256, "float32"),
    (128, 64, 4, 4, True, 256, 256, "float32"),
    (384, 64, 4, 4, True, 256, 256, "float32"),
    (256, 64, 4, 1, True, 256, 256, "float32"),
    (256, 64, 4, 2, True, 256, 256, "float32"),
    (256, 64, 4, 4, True, 256, 256, "bfloat16"),
    (1024, 128, 2, 2, True, 256, 256, "bfloat16"),
    (1024, 128, 4, 1, True, 256, 128, "float32"),
    (2048, 128, 1, 1, True, 128, 256, "float32"),
    (256, 192, 2, 2, True, 256, 256, "bfloat16"),
    (512, 192, 4, 1, False, 256, 256, "float32"),
    (384, 128, 4, 1, False, 256, 256, "bfloat16"),
    (2048, 64, 4, 4, True, 512, 256, "bfloat16"),
    (1024, 128, 2, 2, False, 128, 512, "float32"),
    (384, 192, 4, 4, True, 128, 128, "float32"),
]


class TestFlashAttention:
    def _qkv(self, B=2, T=256, H=4, D=64, dtype=jnp.float32):
        key = jax.random.PRNGKey(0)
        return tuple(jax.random.normal(jax.random.fold_in(key, i),
                                       (B, T, H, D), dtype) for i in range(3))

    @pytest.mark.parametrize(
        "T,D,H,HKV,causal,block_q,block_k,dtype", _FLASH_CASES,
        ids=lambda x: str(x))
    def test_forward_and_grad_parity(self, T, D, H, HKV, causal, block_q,
                                     block_k, dtype):
        """``o``, ``dq``, ``dk``, ``dv`` against the masked softmax in
        float32 (k/v unexpanded through the kernel: dk/dv accumulate over
        the whole query group). bf16 is the production dtype: the dots
        take bf16 inputs with fp32 accumulation, p/ds are downcast before
        the MXU; parity within bf16-rounding tolerances."""
        B = 2 if T <= 512 else 1
        q, _, _ = self._qkv(B=B, T=T, H=H, D=D, dtype=jnp.dtype(dtype))
        _, k, v = self._qkv(B=B, T=T, H=HKV, D=D, dtype=jnp.dtype(dtype))
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        fwd_tol, grad_tol = ((dict(rtol=2e-4, atol=2e-4),
                              dict(rtol=5e-3, atol=5e-4))
                             if dtype == "float32" else
                             (dict(rtol=2e-2, atol=2e-2),
                              dict(rtol=1e-1, atol=0.15)))

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=block_q,
                                   block_k=block_k)

        np.testing.assert_allclose(
            np.asarray(flash(q, k, v), np.float32),
            np.asarray(_masked_softmax_attention(q32, k32, v32, causal)),
            **fwd_tol)

        def loss_f(q, k, v):
            return jnp.sum(flash(q, k, v).astype(jnp.float32) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(_masked_softmax_attention(q, k, v, causal) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q32, k32, v32)
        for a, b in zip(gf, gr):
            assert a.shape == b.shape and a.dtype == q.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), **grad_tol)

    @pytest.mark.slow
    def test_block_512_parity(self):
        """The bench --flash-block 512 A/B rung's tile config is
        numerically identical to the default — fwd AND grad, since the
        rung trains. T=1024 gives 2 blocks per axis so the causal bounds
        (fwd diag_start/diag_end, bwd first_qb/diag_end) are exercised in
        both the unmasked below-diagonal loop and the masked diagonal
        loop at the non-default tile, not just the degenerate 1-block
        case."""
        q, k, v = self._qkv(T=1024)
        o = flash_attention(q, k, v, causal=True, block_q=512, block_k=512)
        o_ref = causal_attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)

        def loss_f(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=512, block_k=512) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(causal_attention_reference(q, k, v) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("what,names", [
        ("forward", {"flash_attention_fwd"}),
        ("grad", {"flash_attention_fwd", "flash_attention_bwd"}),
        ("window", {"flash_attention_window_fwd"}),
    ])
    def test_kernels_by_name(self, what, names):
        """The names the benchmark's readers find the kernels by: one
        forward, ONE backward, the windowed forward its own."""
        q = jax.ShapeDtypeStruct((1, 512, 4, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
        fn = {
            "forward": flash_attention,
            "grad": jax.grad(lambda q, k, v: flash_attention(
                q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
            "window": lambda q, k, v: flash_attention(q, k, v, window=128),
        }[what]
        assert _kernel_names(fn, q, kv, kv) == names

    @pytest.mark.parametrize("unroll,T,D,H,HKV,causal,block_q,block_k,dtype", [
        (0, 2048, 128, 2, 1, True, 256, 256, "bfloat16"),
        (0, 1536, 64, 2, 2, True, 128, 256, "float32"),
        (0, 1024, 128, 2, 2, False, 256, 128, "float32"),
        (0, 384, 192, 2, 2, True, 256, 256, "float32"),
        (8, 1024, 128, 2, 2, False, 256, 128, "float32"),
    ], ids=lambda x: str(x))
    def test_looped_sweep_parity(self, monkeypatch, unroll, T, D, H, HKV,
                                 causal, block_q, block_k, dtype):
        """The sweep of a head too long to write out, forced at sizes the
        interpreter can run: groups of four blocks, then the blocks left
        over (whole groups, leftovers and none of either), and with no
        mask a row of 8 K blocks (a column of 4 Q blocks) written out
        under the looped axis."""
        from deepspeed_tpu.ops.pallas import flash_attention as fa
        monkeypatch.setattr(fa, "_UNROLL_BLOCKS", unroll)
        calls = (fa._fwd_call, fa._bwd_call)   # kept per signature
        for call in calls:
            call.cache_clear()
        try:
            self.test_forward_and_grad_parity(T, D, H, HKV, causal, block_q,
                                              block_k, dtype)
        finally:
            for call in calls:
                call.cache_clear()

    @pytest.mark.parametrize("T,causal,block,body", [
        (2048, True, 256, None), (4096, True, 256, (1, 10)),
        (1024, False, 256, None), (4096, False, 256, (16, 16)),
        (16384, False, 128, (1, 5))])
    def test_sweep_is_written_out_or_looped(self, T, causal, block, body):
        """Each side of the wrapper's thresholds on straight-line code, by
        what the kernels hold. A head of up to ``_UNROLL_BLOCKS`` score
        blocks is written out whole (``body`` None: no loop at all); a
        longer one loops over its q (k) blocks, and ``body`` bounds the
        score blocks written out inside: an unmasked row of up to
        ``_UNROLL_BLOCKS`` whole (16 at T 4096), any other sweep a group
        of ``_GROUP`` and the block left over (under a mask two such
        sweeps), however long the head."""
        x = jax.ShapeDtypeStruct((1, T, 1, 128), jnp.bfloat16)
        fwd = functools.partial(flash_attention, causal=causal,
                                block_q=block, block_k=block)
        grad = jax.grad(lambda q, k, v: fwd(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
        for fn, products in ((fwd, 2), (grad, 2 + 5)):
            text = str(jax.make_jaxpr(fn)(x, x, x))
            assert bool(re.search(r"\b(while|scan)\[", text)) == (
                body is not None)
            if body is not None:
                lo, hi = body
                assert (products * lo <= text.count("dot_general")
                        <= products * hi)

    @pytest.mark.parametrize("T,limit", [(2048, "None"),
                                         (8192, str(25 * 2**20))])
    def test_asks_for_vmem_only_past_the_default(self, T, limit):
        """A head that fits what every kernel gets (16 MiB: the cells'
        T 1024 forward and backward, a T 2048 forward) leaves the
        program's VMEM setting alone; a longer one asks for what it
        counts (its blocks twice, 8 MiB of its own values), not for
        all there is."""
        x = jax.ShapeDtypeStruct((1, T, 1, 128), jnp.bfloat16)
        text = str(jax.make_jaxpr(flash_attention)(x, x, x))
        assert re.findall(r"vmem_limit_bytes=(\w+)", text) == [limit]

    @pytest.mark.parametrize("what,T,fits", [
        ("forward", 32768, True), ("forward", 65536, False),
        ("grad", 16384, True), ("grad", 32768, False)])
    def test_a_head_fits_vmem_or_is_refused(self, what, T, fits):
        """The one threshold of the wrapper: a grid step holds a whole
        head, which fits the chip's VMEM to T 32k forward and 16k
        backward at D 128 in bf16 (the same kernels as at T 256); past it
        the call is refused by name, where the K/V residency failed to
        compile before."""
        x = jax.ShapeDtypeStruct((1, T, 1, 128), jnp.bfloat16)
        fn, names = flash_attention, {"flash_attention_fwd"}
        if what == "grad":
            fn = jax.grad(lambda q, k, v: flash_attention(
                q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))
            names = names | {"flash_attention_bwd"}
        if fits:
            assert _kernel_names(fn, x, x, x) == names
        else:
            with pytest.raises(ValueError, match="ring_attention"):
                jax.eval_shape(fn, x, x, x)

    def test_rejects_ragged_seq(self):
        q, k, v = self._qkv(T=96)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, block_q=128, block_k=64)

    def test_gqa_reference_matches_expanded(self):
        """The jnp oracle's own GQA path vs explicit expansion."""
        q, _, _ = self._qkv(T=128, H=4)
        _, k, v = self._qkv(T=128, H=2)
        o = causal_attention_reference(q, k, v)
        o_ref = causal_attention_reference(q, jnp.repeat(k, 2, axis=2),
                                           jnp.repeat(v, 2, axis=2))
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=1e-6, atol=1e-6)

    def test_gqa_rejects_indivisible_heads(self):
        q, _, _ = self._qkv(T=128, H=4)
        _, k, v = self._qkv(T=128, H=3)
        with pytest.raises(ValueError):
            flash_attention(q, k, v)


    @staticmethod
    def _masked(q, k, v, window=None, sink=None):
        """The softmax written out in float32: causal, a window, and a
        sink as one more logit column whose probability is dropped."""
        B, T, H, D = q.shape
        rep = H // k.shape[2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2)
                       ) / np.sqrt(D)
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        s = jnp.where(seen, s, -1e30)
        if sink is not None:
            s = jnp.concatenate([s, jnp.broadcast_to(
                sink[None, :, None, None], (B, H, T, 1))], -1)
        p = jax.nn.softmax(s, -1)[..., :T]
        return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, rep, 2))

    @pytest.mark.parametrize("window,sink", [
        (None, False), (128, False), (128, True), (100, True), (1, True),
        (300, True)], ids=["full", "window", "window-sink", "w100-sink",
                           "w1-sink", "w300-sink"])
    @pytest.mark.parametrize("D,Dv,H,HKV", [(192, 128, 8, 2), (64, 128, 4, 4),
                                            (128, 128, 4, 2)],
                             ids=["k192-v128", "k64-v128", "k128-v128"])
    def test_values_of_their_own_width_and_a_sink(self, D, Dv, H, HKV,
                                                  window, sink):
        """``v [.., D_v]`` beside ``q, k [.., D]`` in both forwards, and
        the sink in the windowed one (a window of ONE K block, inside a
        block, of one position, across blocks), against the softmax
        written out (interpret mode)."""
        key = jax.random.PRNGKey(D + Dv)
        q = jax.random.normal(jax.random.fold_in(key, 0), (1, 512, H, D))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 512, HKV, D))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 512, HKV, Dv))
        b = 1.0 + jax.random.normal(jax.random.fold_in(key, 3), (H,)) \
            if sink else None
        got = flash_attention(q, k, v, window=window, sink=b, block_q=128,
                              block_k=128)
        assert got.shape == (1, 512, H, Dv)
        np.testing.assert_allclose(got, self._masked(q, k, v, window, b),
                                   atol=5e-6)

    def test_what_has_no_backward_or_no_place_says_so_by_name(self):
        x = jnp.ones((1, 128, 2, 64), jnp.float32)
        v = jnp.ones((1, 128, 2, 32), jnp.float32)
        b = jnp.zeros((2,), jnp.float32)
        with pytest.raises(NotImplementedError, match="D_v"):
            jax.grad(lambda q: flash_attention(q, x, v).sum())(x)
        with pytest.raises(NotImplementedError, match="sink"):
            jax.grad(lambda q: flash_attention(q, x, x, window=64,
                                               sink=b).sum())(x)
        with pytest.raises(ValueError, match="sink"):       # full forward
            flash_attention(x, x, x, sink=b)
        with pytest.raises(ValueError, match="sink"):       # one a head
            flash_attention(x, x, x, window=64, sink=jnp.zeros((3,)))
        with pytest.raises(ValueError, match="D_v"):        # k's width
            flash_attention(x, v, v)
        # D_v == D and no sink: the kernel and its backward as they were
        g = jax.grad(lambda q: flash_attention(q, x, x).sum())(x)
        assert g.shape == x.shape


class TestDecodeAttention:
    @pytest.mark.parametrize("block_k", [128, 256])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                           (jnp.bfloat16, 3e-2)],
                             ids=["fp32", "bf16"])
    def test_parity_with_ragged_lengths(self, dtype, tol, block_k):
        # a dense cache is a one-layer pool with an identity table: the
        # lengths sit on every edge of a block, and the rows beyond a
        # length hold garbage that a missing bound would let in
        B, H, S, D = 6, 4, 512, 64
        key = jax.random.PRNGKey(1)
        lengths = jnp.asarray([1, block_k - 1, block_k, block_k + 1, 200,
                               S], jnp.int32)
        dead = (jnp.arange(S)[None, :] >= lengths[:, None])[..., None, None]
        q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, D))
        kc = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
        vc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, D))
        q, kc, vc = (x.astype(dtype) for x in (q, kc, vc))
        o = decode_attention(q, jnp.where(dead, 3e4, kc).astype(dtype),
                             jnp.where(dead, -3e4, vc).astype(dtype),
                             lengths, block_k=block_k)
        o_ref = decode_attention_reference(
            q.astype(jnp.float32), kc.astype(jnp.float32),
            vc.astype(jnp.float32), lengths)
        np.testing.assert_allclose(np.asarray(o.astype(jnp.float32)),
                                   np.asarray(o_ref), rtol=tol, atol=tol)

    def test_single_token_is_value(self):
        # with length 1, the output must equal v_cache[:, :, 0]
        B, H, S, D = 2, 2, 256, 64
        key = jax.random.PRNGKey(2)
        q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, D))
        kc = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
        vc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, D))
        lengths = jnp.ones((B,), jnp.int32)
        o = decode_attention(q, kc, vc, lengths)
        np.testing.assert_allclose(np.asarray(o), np.asarray(vc[:, 0]),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("H,KH", [(8, 2), (8, 4), (48, 12)],
                             ids=["rep4", "rep2", "a-product-a-head"])
    def test_gqa_native_groups(self, H, KH):
        # query heads in groups over KH kv heads: the kernel must match
        # the expanded reference WITHOUT materializing repeated k/v,
        # whether the heads share one product (few rows in all) or not
        B, S, D = 2, 256, 64
        key = jax.random.PRNGKey(3)
        q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, D))
        kc = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KH, D))
        vc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KH, D))
        lengths = jnp.asarray([64, 256], jnp.int32)
        o = decode_attention(q, kc, vc, lengths)
        o_ref = decode_attention_reference(q, kc, vc, lengths)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)


class TestFusedLayerNorm:
    def test_forward_parity(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 256))
        w = jax.random.normal(jax.random.PRNGKey(1), (256,)) + 1.0
        b = jax.random.normal(jax.random.PRNGKey(2), (256,))
        o = fused_layer_norm(x, w, b)
        o_ref = layer_norm_reference(x, w, b)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_parity(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        w = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0
        b = jnp.zeros((128,))

        def loss_f(x, w, b):
            return jnp.sum(fused_layer_norm(x, w, b) ** 2)

        def loss_r(x, w, b):
            return jnp.sum(layer_norm_reference(x, w, b) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, w, b)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def test_residual_variant(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 128))
        r = jax.random.normal(jax.random.PRNGKey(1), (4, 128))
        w = jnp.ones((128,))
        b = jnp.zeros((128,))
        o, s = fused_residual_layer_norm(x, r, w, b)
        np.testing.assert_allclose(np.asarray(s), np.asarray(x + r))
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(layer_norm_reference(x + r, w, b)),
            rtol=1e-5, atol=1e-5)


class TestQuantizer:
    def test_symmetric_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
        q, scale = quantize_symmetric(x, groups=16)
        y = dequantize_symmetric(q, scale, groups=16)
        # int8 roundtrip error bounded by scale/2 per group
        err = np.abs(np.asarray(x) - np.asarray(y))
        bound = np.asarray(scale)[:, None] * 0.5 + 1e-6
        assert (err <= bound).all()

    def test_asymmetric_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128)) + 3.0
        q, scale, zero = quantize_asymmetric(x, groups=8)
        y = dequantize_asymmetric(q, scale, zero, groups=8)
        err = np.abs(np.asarray(x) - np.asarray(y))
        bound = np.asarray(scale)[:, None] * 0.5 + 1e-6
        assert (err <= bound).all()

    def test_stochastic_rounding_unbiased(self):
        x = jnp.full((1, 1024), 0.3)  # value between int steps
        vals = []
        for s in range(20):
            q, scale = quantize_symmetric(x, groups=1, bits=8,
                                          rng=jax.random.PRNGKey(s))
            vals.append(float(dequantize_symmetric(q, scale, 1).mean()))
        # stochastic rounding mean should approach the true value
        assert abs(np.mean(vals) - 0.3) < 0.02

    def test_quantizer_object(self):
        qz = Quantizer(q_bits=8, q_groups=4, symmetric=True)
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 256))
        y = qz.fake_quantize(x)
        assert y.shape == x.shape
        assert float(jnp.abs(y - x).max()) < 0.1


class TestRandomLTD:
    def test_gather_scatter_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 8))
        idx = random_ltd.sample_token_indices(jax.random.PRNGKey(1), 16, 8, 2)
        part = random_ltd.token_gather(x, idx)
        assert part.shape == (2, 8, 8)
        # indices are sorted unique
        assert (np.diff(np.asarray(idx), axis=1) > 0).all()
        back = random_ltd.token_scatter(x, part, idx)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x))

    def test_layer_passthrough(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4))
        out = random_ltd.random_ltd_layer(
            lambda t: t * 2.0, x, jax.random.PRNGKey(1), keep=4)
        doubled = np.isclose(np.asarray(out), 2 * np.asarray(x)).all(axis=-1)
        kept_counts = doubled.sum(axis=1)
        assert (kept_counts == 4).all()

    def test_gpt_mask(self):
        idx = jnp.asarray([[0, 3, 5]])
        mask = random_ltd.gpt_attention_mask(idx, 8)
        expected = np.array([[[1, 0, 0], [1, 1, 0], [1, 1, 1]]], bool)
        np.testing.assert_array_equal(np.asarray(mask), expected)
