"""Trace-only guards for model configurations at their real shapes:
jax.eval_shape runs the FULL model trace (remat, MoE dispatch,
flash-attention custom_vjp wiring) without allocating or compiling — a
trace-time crash here is exactly what would eat a chip call (a remat+MoE
TracerBoolConversionError is what this file was written after)."""
import jax
import jax.numpy as jnp


def _trace_train(model, global_batch, seq):
    shapes = jax.eval_shape(lambda r: model.init(r), jax.random.PRNGKey(0))
    batch = {"input_ids": jax.ShapeDtypeStruct((global_batch, seq),
                                               jnp.int32)}

    def step(p, b):
        return model.loss_fn(p, b, jax.random.PRNGKey(1))

    out = jax.eval_shape(jax.value_and_grad(step), shapes, batch)
    loss_shape = out[0]
    assert loss_shape.shape == ()


def test_train_moe_125m_e8_traces():
    """gpt2-125m + 8 experts every other layer, micro 8, seq 1024,
    remat+flash on (the model's defaults)."""
    from deepspeed_tpu.models.gpt2 import GPT2LMModel, config_for
    cfg = config_for("gpt2-125m", n_positions=1024, dtype=jnp.bfloat16,
                     num_experts=8)
    _trace_train(GPT2LMModel(cfg), global_batch=8, seq=1024)


def test_train_llama_1b_traces():
    """llama-1b at micro 4 x seq 2048 (the streamed-offload engine
    wrapping is TPU-only, but every model-level trace hazard shows up
    here)."""
    from deepspeed_tpu.models.llama import LlamaLMModel, config_for
    cfg = config_for("llama-1b", n_positions=2048, dtype=jnp.bfloat16)
    _trace_train(LlamaLMModel(cfg), global_batch=4, seq=2048)


def test_train_350m_int8_traces():
    """gpt2-350m with SwitchBack projections + flash + remat at micro 8,
    seq 1024 (custom-VJP int8 dot inside remat is the trace hazard this
    guards)."""
    from deepspeed_tpu.models.gpt2 import GPT2LMModel, config_for
    cfg = config_for("gpt2-350m", n_positions=1024, dtype=jnp.bfloat16,
                     int8_training=True)
    _trace_train(GPT2LMModel(cfg), global_batch=8, seq=1024)


def test_train_350m_flash_seq8k_traces():
    """gpt2-350m at one row of 8192 tokens (the flash kernels' looped
    sweep, not the written-out one)."""
    from deepspeed_tpu.models.gpt2 import GPT2LMModel, config_for
    cfg = config_for("gpt2-350m", n_positions=8192, dtype=jnp.bfloat16)
    _trace_train(GPT2LMModel(cfg), global_batch=1, seq=8192)


def test_autotune_grid_envelope_traces():
    """The autotuner's most extreme grid point for gpt2-350m (micro 16,
    flash block 512) must trace — a trace-time crash inside one trial
    would end the whole session."""
    from deepspeed_tpu.models.gpt2 import GPT2LMModel, config_for
    cfg = config_for("gpt2-350m", n_positions=1024, dtype=jnp.bfloat16,
                     flash_block=512)
    _trace_train(GPT2LMModel(cfg), global_batch=16, seq=1024)


def test_chained_flash_and_matmul_loops_trace():
    """The flash kernel chained through a fori_loop, forward and
    backward, and a chained matmul trace on CPU (eval_shape only —
    interpret-mode pallas inside a loop would crawl)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    B, T, H, D = 2, 256, 4, 64
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)

    def chained(q, k, v):
        def body(_, qq):
            return flash_attention(qq, k, v, causal=True)
        return jax.lax.fori_loop(0, 3, body, q)

    out = jax.eval_shape(chained, q, q, q)
    assert out.shape == (B, T, H, D)

    # the chained-grad (bwd sustained) loop traces too: dq feeds the
    # next query through jax.grad over the custom-vjp kernel
    def floss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    def chained_bwd(q, k, v):
        def body(_, qq):
            dq, dk, dv = jax.grad(floss, argnums=(0, 1, 2))(qq, k, v)
            # dk/dv consumed so the dkv kernel can't be DCE'd out of the
            # loop
            return dq + (jnp.sum(dk) + jnp.sum(dv)).astype(dq.dtype) * \
                jnp.asarray(1e-30, dq.dtype)
        return jax.lax.fori_loop(0, 2, body, q)

    out = jax.eval_shape(chained_bwd, q, q, q)
    assert out.shape == (B, T, H, D)

    def mm(x, w):
        def body(_, xx):
            return jax.lax.dot(xx, w, preferred_element_type=jnp.bfloat16)
        return jax.lax.fori_loop(0, 3, body, x)

    a = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    assert jax.eval_shape(mm, a, a).shape == (512, 512)


def test_effective_block_is_the_block_the_kernel_runs():
    """flash fit shrinks the block to the largest power-of-two fraction
    >= 128 that tiles seq — NOT a plain min: block 512 at seq 768 runs
    256."""
    from deepspeed_tpu.ops.pallas.flash_attention import effective_block
    assert effective_block(512, 768) == 256       # 768 % 512
    assert effective_block(512, 1024) == 512
    assert effective_block(512, 256) == 256       # clamp
    # non-power-of-two request whose halvings miss every divisor snaps
    # to the 128 floor (the block the kernel actually runs), never to a
    # fictitious sub-128 tile
    assert effective_block(384, 512) == 128
