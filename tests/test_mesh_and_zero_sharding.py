"""Mesh construction + ZeRO sharding-policy unit tests (pure placement
logic — the analog of the reference's topology tests,
tests/unit/runtime/pipe/test_topology.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import (MeshConfig, build_mesh,
                                     get_data_parallel_world_size,
                                     get_model_parallel_world_size,
                                     get_pipe_parallel_world_size)
from deepspeed_tpu.runtime.zero.partition import (ZeroShardingPolicy,
                                                  shard_leaf_spec)


def test_default_mesh_all_data():
    mesh = build_mesh(MeshConfig())
    assert get_data_parallel_world_size(mesh) == 8
    assert get_model_parallel_world_size(mesh) == 1


def test_mesh_2d():
    mesh = build_mesh(MeshConfig(data=4, tensor=2))
    assert get_data_parallel_world_size(mesh) == 4
    assert get_model_parallel_world_size(mesh) == 2


def test_mesh_3d():
    mesh = build_mesh(MeshConfig(data=2, tensor=2, pipe=2))
    assert get_data_parallel_world_size(mesh) == 2
    assert get_pipe_parallel_world_size(mesh) == 2


def test_mesh_indivisible_raises():
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(data=3, tensor=2))


def test_shard_leaf_picks_largest_divisible_dim(mesh8):
    spec = shard_leaf_spec((128, 512), None, mesh8)
    assert spec == P(None, "data")
    spec = shard_leaf_spec((1024, 16), None, mesh8)
    assert spec == P("data", None)


def test_shard_leaf_respects_tp_claim():
    mesh = build_mesh(MeshConfig(data=4, tensor=2))
    # dim1 claimed by tensor; ZeRO must take dim0
    spec = shard_leaf_spec((64, 128), P(None, "tensor"), mesh)
    assert spec == P("data", "tensor")


def test_shard_leaf_small_stays_replicated(mesh8):
    assert shard_leaf_spec((3,), None, mesh8) == P()
    assert shard_leaf_spec((7, 5), None, mesh8) == P()


params = {"dense": {"kernel": jnp.zeros((64, 128)), "bias": jnp.zeros((128,))},
          "emb": jnp.zeros((256, 64))}


@pytest.mark.parametrize("stage,param_sharded,grad_sharded,master_sharded", [
    (0, False, False, False),
    (1, False, False, True),
    (2, False, True, True),
    (3, True, True, True),
])
def test_policy_stages(mesh8, stage, param_sharded, grad_sharded,
                       master_sharded):
    policy = ZeroShardingPolicy(stage, mesh8)

    def is_sharded(sh_tree):
        kernel_spec = sh_tree["dense"]["kernel"].spec
        return any(e is not None for e in kernel_spec)

    assert is_sharded(policy.param_sharding(params)) == param_sharded
    assert is_sharded(policy.grad_sharding(params)) == grad_sharded
    assert is_sharded(policy.master_sharding(params)) == master_sharded


def test_policy_stage3_with_tp():
    mesh = build_mesh(MeshConfig(data=4, tensor=2))
    tp = {"dense": {"kernel": P(None, "tensor"), "bias": P()}, "emb": None}
    policy = ZeroShardingPolicy(3, mesh, tp_specs=tp)
    sh = policy.param_sharding(params)
    assert sh["dense"]["kernel"].spec == P("data", "tensor")
    assert sh["emb"].spec in (P("data", None), P(None, "data"))


def test_sharded_array_memory_footprint(mesh8):
    """Stage-3 params must actually occupy 1/8 of the bytes per device."""
    policy = ZeroShardingPolicy(3, mesh8)
    sh = policy.param_sharding(params)
    x = jax.device_put(params["emb"], sh["emb"])
    shard = x.addressable_shards[0]
    assert shard.data.size == x.size // 8


class TestTiledLinear:
    """runtime/zero/tiling.py TiledLinear (reference zero/tiling.py —
    SURVEY row 15): dense parity across tile grids, from_dense, and the
    return-bias variant."""

    def test_matches_dense(self):
        import numpy as np
        from deepspeed_tpu.runtime.zero.tiling import TiledLinear
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(jax.random.fold_in(rng, 1), (4, 48))
        kernel = jax.random.normal(jax.random.fold_in(rng, 2), (48, 36)) * 0.1
        bias = jax.random.normal(jax.random.fold_in(rng, 3), (36,)) * 0.1
        dense = x @ kernel + bias
        for in_s, out_s in [(1, 1), (3, 2), (4, 3), (48, 36)]:
            tl = TiledLinear(48, 36, in_splits=in_s, out_splits=out_s)
            y = tl.apply(tl.from_dense(kernel, bias), x)
            np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                                       rtol=1e-5, atol=1e-5)

    def test_grad_parity_and_leaf_granularity(self):
        import numpy as np
        from deepspeed_tpu.runtime.zero.tiling import TiledLinear
        tl = TiledLinear(16, 12, in_splits=2, out_splits=3)
        params = tl.init(jax.random.PRNGKey(0))
        assert len([k for k in params if k.startswith("w_")]) == 6
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))

        def loss(p):
            return jnp.sum(tl.apply(p, x) ** 2)
        g = jax.grad(loss)(params)
        kernel = jnp.concatenate(
            [jnp.concatenate([params[f"w_{i}_{j}"] for j in range(3)], 1)
             for i in range(2)], 0)
        bias = jnp.concatenate([params[f"b_{j}"] for j in range(3)])

        def dense_loss(k, b):
            return jnp.sum((x @ k + b) ** 2)
        gk, gb = jax.grad(dense_loss, argnums=(0, 1))(kernel, bias)
        np.testing.assert_allclose(np.asarray(g["w_0_0"]),
                                   np.asarray(gk[:8, :4]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g["b_2"]),
                                   np.asarray(gb[8:]), rtol=1e-5)

    def test_split_input_and_return_bias(self):
        import numpy as np
        from deepspeed_tpu.runtime.zero.tiling import (
            TiledLinear, TiledLinearReturnBias, split_tensor_along_last_dim)
        x = jax.random.normal(jax.random.PRNGKey(2), (3, 20))
        tl = TiledLinear(20, 10, in_splits=4, out_splits=2,
                         input_is_already_split=True)
        params = tl.init(jax.random.PRNGKey(3))
        y = tl.apply(params, split_tensor_along_last_dim(x, 4))
        tl2 = TiledLinear(20, 10, in_splits=4, out_splits=2)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(tl2.apply(params, x)),
                                   rtol=1e-6)
        rb = TiledLinearReturnBias(20, 10, in_splits=4, out_splits=2)
        yn, b = rb.apply(params, x)
        np.testing.assert_allclose(np.asarray(yn + b), np.asarray(y),
                                   rtol=1e-6)
        nb = TiledLinearReturnBias(20, 10, bias=False, in_splits=2,
                                   out_splits=2)
        yn2, b2 = nb.apply(nb.init(jax.random.PRNGKey(4)), x)
        assert b2 is None


def test_uneven_non_expert_tp_dim_warns_not_raises(caplog):
    """ADVICE r3: GSPMD pads ragged shards of plain matmul/embedding
    params, so an unpadded vocab dim on the tensor axis must warn (about
    the padding waste), not refuse at engine init. The hard error stays
    for expert dims (test_llama_moe pins it) where the dispatch
    all-to-all genuinely needs equal shards."""
    import logging
    from deepspeed_tpu.utils.logging import logger as ds_logger
    mesh = build_mesh(MeshConfig(data=4, tensor=2))
    uneven = {"emb": jnp.zeros((251, 8))}  # 251 % 2 != 0
    tp = {"emb": P("tensor", None)}
    policy = ZeroShardingPolicy(1, mesh, tp_specs=tp)
    ds_logger.propagate = True  # caplog listens on root
    try:
        with caplog.at_level(logging.WARNING):
            sh = policy.param_sharding(uneven)
    finally:
        ds_logger.propagate = False
    assert sh["emb"].spec == P("tensor", None)
    assert any("not divisible" in r.getMessage() for r in caplog.records)


# ---------------------------------------------------------------------------
# utils/sharding.maybe_constrain: which mesh a model's bare spec means.
# The engine traces its step with explicit NamedShardings and opens no
# mesh context, so a constraint has to find the engine's mesh itself.
# ---------------------------------------------------------------------------

RESIDUAL = P(("data", "fsdp"), "seq", None)


def _constraints(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("sharding_constraint")


@pytest.mark.parametrize("axes,spec,rows,emitted", [
    (dict(data=2, fsdp=4), RESIDUAL, 8, True),      # ZeRO-3 over fsdp
    (dict(data=8), RESIDUAL, 8, True),
    (dict(data=1, seq=8), RESIDUAL, 8, True),       # `seq` alone shards T
    (dict(data=1, tensor=8), RESIDUAL, 8, False),   # every named axis 1
    (dict(data=1, fsdp=1), RESIDUAL, 8, False),     # one chip
    (dict(data=2, fsdp=4), P("nowhere", None, None), 8, False),
    # a trained model applied to two rows under the engine's old mesh
    (dict(data=2, fsdp=4), RESIDUAL, 2, False),
    (None, RESIDUAL, 8, False),                     # no engine, bare jit
], ids=["fsdp4", "data8", "seq8", "tensor8", "one-chip", "unknown-axis",
        "batch-does-not-divide", "no-mesh"])
def test_maybe_constrain_finds_the_engines_mesh(axes, spec, rows, emitted):
    from deepspeed_tpu.comm.mesh import set_global_mesh
    from deepspeed_tpu.utils.sharding import engine_mesh, maybe_constrain
    if axes is not None:
        n = int(np.prod(list(axes.values())))
        set_global_mesh(build_mesh(MeshConfig(**axes),
                                   devices=jax.devices()[:n]))
    assert jax.sharding.get_abstract_mesh().empty
    assert (engine_mesh() is None) == (axes is None)
    x = jnp.zeros((rows, 16, 4))
    fn = lambda x: maybe_constrain(x, spec) * 2.0  # noqa: E731
    assert _constraints(fn, x) == int(emitted)
    if emitted:
        # over the ENGINE's mesh, though nothing opened a context
        y = jax.jit(lambda x: maybe_constrain(x, spec))(x)
        assert y.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(engine_mesh(), spec), x.ndim)
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(x)), 0.0)


@pytest.mark.parametrize("manual", [("data", "fsdp", "seq"), ("pipe",)],
                         ids=["over-named-axes", "over-another-axis"])
def test_maybe_constrain_inside_shard_map(manual):
    """In a manual region the caller owns the layout: over the axes the
    spec names the helper returns ``x`` (XLA rejects the constraint);
    over another axis the named ones are still Auto and the context's
    mesh, not the global one, carries the bare spec."""
    from deepspeed_tpu.comm.mesh import set_global_mesh
    from deepspeed_tpu.utils.sharding import engine_mesh, maybe_constrain
    mesh = build_mesh(MeshConfig(pipe=2, data=1, fsdp=2, seq=2))
    set_global_mesh(mesh)
    seen = []

    def body(x):
        seen.append(engine_mesh())
        return maybe_constrain(x, RESIDUAL) + 1.0
    mapped = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                           axis_names=set(manual), check_vma=False)
    x = jnp.zeros((8, 16, 4))
    assert _constraints(mapped, x) == int(manual == ("pipe",))
    assert seen == [None]
    np.testing.assert_array_equal(np.asarray(jax.jit(mapped)(x)), 1.0)


def test_maybe_constrain_under_a_mesh_context_is_the_contexts():
    """``jax.set_mesh`` rules where a caller opened it (the abstract mesh,
    a bare spec), whatever mesh an engine left behind."""
    from deepspeed_tpu.comm.mesh import set_global_mesh
    from deepspeed_tpu.utils.sharding import maybe_constrain
    set_global_mesh(build_mesh(MeshConfig(data=1, tensor=8)))
    mesh = build_mesh(MeshConfig(data=2, fsdp=4))
    x = jnp.zeros((8, 16, 4))
    with jax.set_mesh(mesh):
        assert _constraints(lambda x: maybe_constrain(x, RESIDUAL), x) == 1
        y = jax.jit(lambda x: maybe_constrain(x, RESIDUAL))(x)
    assert y.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(mesh, RESIDUAL), x.ndim)


@pytest.mark.parametrize("stage", [0, 3])
def test_engine_step_holds_the_models_constraint(stage):
    """The residual-stream constraint of ``models/gpt2.py`` is IN the
    engine's traced step (it was a silent no-op while only an abstract
    mesh could switch it on), and the step computes the loss it did."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMModel, config_for
    model = GPT2LMModel(config_for("gpt2-125m", n_embd=32, n_layer=2,
                                   n_head=2, vocab_size=64, n_positions=16,
                                   dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    batch = {"input_ids": np.arange(8 * 16, dtype=np.int32).reshape(
        8, 16) % 64}
    want = float(model.loss_fn(params, batch))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        mesh=build_mesh(MeshConfig(data=2, fsdp=4)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": stage}})
    metrics = engine.train_batch(batch)
    np.testing.assert_allclose(float(metrics["loss"]), want, rtol=1e-5)
    placed = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        batch, engine._batch_sharding(batch))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    jaxpr = str(jax.make_jaxpr(engine._step_fn, static_argnums=3)(
        engine.state, placed, rng, False))
    assert "sharding_constraint" in jaxpr
