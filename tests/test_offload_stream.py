"""The streamed offload's update path (``runtime/zero/offload_stream.py``)
on the CPU: its arithmetic against the whole-tree update. Its ORDER on
the chip is ``tests/test_tpu_aot_compile.py``'s (compiled for a
described v5e); the refusals of ``implementation: "stream"`` are in
``tests/test_offload_and_native_ops.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.parametrize("case", ["bf16_master", "bf16_master_numerics",
                                  "float32_no_master"])
def test_stream_pipeline_is_the_whole_tree_update_to_the_bit(case):
    """The stream's update path (``offload_stream.streamed_update``, what
    ``engine._make_step_fn`` calls when it streams) with its two
    ``device_put``s replaced by identities, against the whole-tree
    update every other configuration runs (``do_update``: one
    ``optimizer.update``, ``master + updates``, the bf16 cast,
    ``block_sq_norms``): three steps of AdamW with weight decay over
    leaves of several ranks and sizes give the same master, mu, nu,
    counter, bf16 parameters and per-block update norms, bit for bit."""
    from deepspeed_tpu.ops.adam import adam
    from deepspeed_tpu.runtime.precision import cast_tree
    from deepspeed_tpu.runtime.zero.offload_stream import streamed_update
    from deepspeed_tpu.telemetry.numerics import block_spec, block_sq_norms
    mixed = case != "float32_no_master"
    numerics = case == "bf16_master_numerics"
    shapes = {"wte": (96, 16), "h_0": {"w": (16, 48), "b": (48,)},
              "h_1": {"w": (16, 16), "b": (16,), "scale": ()},
              "conv": (3, 3, 4, 8), "ln_f": {"g": (16,), "b": (16,)}}
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    rand = lambda scale: jax.tree.map(  # noqa: E731
        lambda shp: scale * jax.random.normal(next(keys), shp, jnp.float32),
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    master = rand(0.5)
    opt = adam(weight_decay=0.1)
    spec = block_spec(master) if numerics else None
    everywhere = jax.tree.map(lambda _: None, master)   # identities ignore it
    opt_state = opt.init(master)
    opt_sh = jax.tree.map(lambda _: None, opt_state)
    dtype = jnp.bfloat16 if mixed else None

    @jax.jit
    def whole(grads, master, opt_state, lr):
        updates, new_opt = opt.update(grads, opt_state, master, lr)
        new_master = jax.tree.map(jnp.add, master, updates)
        params = cast_tree(new_master, dtype) if mixed else None
        upd_sq = block_sq_norms(updates, spec) if numerics else ()
        return new_master, new_opt, params, upd_sq

    @jax.jit
    def streamed(grads, master, opt_state, lr):
        return streamed_update(
            opt, grads, master, opt_state, lr,
            master_sh=everywhere if mixed else None, opt_sh=opt_sh,
            compute_dtype=dtype, upd_sq_spec=spec,
            fetch=lambda x, _: x, store=lambda x, _: x)

    a = b = (master, opt_state)
    for step in range(3):
        grads, lr = rand(1.0 + step), jnp.float32(1e-2 / (step + 1))
        a = whole(grads, a[0], a[1], lr)
        b = streamed(grads, b[0], b[1], lr)
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(b[1].count) == 3
