"""Kernel/op tests — numerical parity against jnp oracles (the reference's
tests/unit/ops strategy: each op vs a torch/numpy reference)."""
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


class TestFlashAttention:
    def _qkv(self, B=2, T=256, H=4, D=64, dtype=jnp.float32):
        key = jax.random.PRNGKey(0)
        return tuple(jax.random.normal(jax.random.fold_in(key, i),
                                       (B, T, H, D), dtype) for i in range(3))

    def test_forward_parity(self):
        q, k, v = self._qkv()
        o = flash_attention(q, k, v, causal=True)
        o_ref = causal_attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.slow
    def test_block_512_parity(self):
        """The bench --flash-block 512 A/B rung's tile config is
        numerically identical to the default — fwd AND grad, since the
        rung trains. T=1024 gives 2 blocks per axis so the causal bounds
        (fwd diag_start/num_kb, bwd first_qb/diag_end) are exercised in
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

    def test_noncausal_parity(self):
        q, k, v = self._qkv(T=128)
        o = flash_attention(q, k, v, causal=False)
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
        p = jax.nn.softmax(att, axis=-1)
        o_ref = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_grad_parity(self):
        q, k, v = self._qkv(T=128)

        def loss_f(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(causal_attention_reference(q, k, v) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)

    def test_rejects_ragged_seq(self):
        q, k, v = self._qkv(T=96)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, block_q=128, block_k=64)

    def test_block_fallback_on_128_multiples(self):
        """The 256 defaults must not reject T that only divides by 128
        (callers gate flash on T % 128 == 0 — ops/transformer.py:163)."""
        q, k, v = self._qkv(T=384)
        o = flash_attention(q, k, v, causal=True)
        o_ref = causal_attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("hkv", [1, 2])
    def test_gqa_forward_and_grad_parity(self, hkv):
        """Grouped-query attention: unexpanded k/v ([B, T, HKV, D],
        HKV | H) through the kernel must equal the expanded-MHA oracle,
        including dk/dv (which accumulate over the whole query group)."""
        q, _, _ = self._qkv(T=256, H=4)
        _, k, v = self._qkv(T=256, H=hkv)
        rep = 4 // hkv
        kx = jnp.repeat(k, rep, axis=2)
        vx = jnp.repeat(v, rep, axis=2)

        o = flash_attention(q, k, v, causal=True)
        o_ref = causal_attention_reference(q, kx, vx)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)

        def loss_f(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def loss_r(q, k, v):
            o = causal_attention_reference(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))
            return jnp.sum(o ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)

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

    def test_bf16_forward_and_grad_parity(self):
        """The production dtype: kernel dots take bf16 inputs with fp32
        accumulation; p/ds are downcast before the MXU dots. Parity vs the
        fp32 reference within bf16-rounding tolerances."""
        q, k, v = self._qkv(T=256, dtype=jnp.bfloat16)
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))

        o = flash_attention(q, k, v, causal=True)
        o_ref = causal_attention_reference(q32, k32, v32)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref), rtol=2e-2, atol=2e-2)

        def loss_f(q, k, v):
            return jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(causal_attention_reference(q, k, v) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q32, k32, v32)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), rtol=1e-1, atol=0.15)


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
