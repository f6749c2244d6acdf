"""The compiler's rematerialisation pass counts a step's state in pinned
host memory against the chip; the engine gives that share back as a
compiler option of ``train_step``
(``runtime/activation_checkpointing.remat_limit_percent``,
``engine._remat_limit_percent``; PERF.md section 6, PR 48)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMModel
from deepspeed_tpu.runtime.activation_checkpointing import (
    REMAT_LIMIT_DEFAULT_PERCENT, REMAT_LIMIT_OPTION, remat_limit_percent)

# one v5e's memory_stats()["bytes_limit"] (my chip run, PR 48)
V5E = 16_909_336_064
N_1_3B = 1_313_722_368          # GPT-2 1.3B, vocabulary padded to 50304


@pytest.mark.parametrize("bytes_limit, host_bytes, percent", [
    # the offload cell: float32 master and both moments on the host,
    # 15.76 GB = 93 % of the chip (the pass's log at 188: a limit of
    # 29.22GiB less 17.13GiB of outputs, where 95 left 0B)
    (V5E, 12 * N_1_3B, 188),
    # the x4 cell a chip: nothing on the host, the option is not set
    (V5E, 0, None),
    # a limit nobody knows sets nothing: today's program
    (0, 12 * N_1_3B, None),
    (0, 0, None),
    # the moments alone on the host
    (V5E, 8 * N_1_3B, REMAT_LIMIT_DEFAULT_PERCENT + 62),
], ids=["offload", "x4-a-chip", "offload-limit-unknown",
        "x4-limit-unknown", "moments-only"])
def test_the_share_given_back_on_the_train_cells_shapes(
        bytes_limit, host_bytes, percent):
    assert remat_limit_percent(bytes_limit, host_bytes) == percent


# -------------------------------------------------------------- the engine

VOCAB = 256


def batch(rows=4, seq=32):
    rng = np.random.default_rng(0)
    return {"input_ids": jnp.asarray(
        rng.integers(0, VOCAB, size=(rows, seq)), jnp.int32)}


def _engine():
    model = GPT2LMModel(GPT2Config(
        vocab_size=VOCAB, n_positions=64, n_embd=64, n_layer=2, n_head=4,
        dtype=jnp.float32, use_flash_attention=False,
        vocab_pad_multiple=64))
    params = model.init(jax.random.PRNGKey(0), batch_size=2, seq_len=32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}})
    return engine


def _gauge(engine, name):
    (series,) = engine.telemetry.snapshot()[name]["series"]
    return series["value"]


@pytest.mark.parametrize("on_host, bytes_limit", [
    (True, 10**6), (True, None), (False, 10**6)],
    ids=["host-state", "host-state-limit-unknown", "no-host-state"])
def test_the_engine_counts_what_it_placed_on_the_host(
        on_host, bytes_limit, monkeypatch):
    """The optimizer state's shardings say where it lives; a chip's
    bytes of what lives in pinned host memory, over the chip's limit,
    are added to the default share. The CPU backend keeps no account of
    its memory (``memory_stats()`` None): an unknown limit sets nothing."""
    engine = _engine()
    opt_bytes = 0
    if on_host:
        opt_bytes = sum(x.nbytes for x in
                        jax.tree.leaves(engine.state.opt_state))
        assert opt_bytes > 0
        monkeypatch.setattr(
            engine, "_state_shardings", engine._state_shardings.replace(
                opt_state=jax.tree.map(
                    lambda s: s.with_memory_kind("pinned_host"),
                    engine._state_shardings.opt_state)))
    percent = engine._remat_limit_percent(bytes_limit)
    if on_host and bytes_limit:
        # stage 0: the state is replicated, a chip holds all of it
        assert percent == REMAT_LIMIT_DEFAULT_PERCENT \
            + 100 * opt_bytes // bytes_limit
    else:
        assert percent is None
    assert _gauge(engine, "train_remat_limit_percent") == (percent or 0)


@pytest.mark.parametrize("percent", [None, 188])
def test_the_share_is_a_compiler_option_of_the_step(percent, monkeypatch):
    """Where state lives on the host the step is compiled with the
    share; where none does, with no such option (the x4 cell's text
    stays what it was)."""
    from deepspeed_tpu import telemetry
    engine = _engine()
    seen = []

    def recorder(fn, **kw):
        seen.append(kw)
        return fn
    monkeypatch.setattr(telemetry, "watched_jit", recorder)
    monkeypatch.setattr(engine, "_remat_limit_percent", lambda limit: percent)
    engine._compile_step(batch())
    if percent is None:
        assert "compiler_options" not in seen[-1]
    else:
        assert seen[-1]["compiler_options"] == {REMAT_LIMIT_OPTION: percent}


def test_a_step_on_the_cpu_sets_nothing():
    engine = _engine()
    metrics = engine.train_batch(batch(rows=2 * 2 * jax.device_count()))
    assert np.isfinite(float(metrics["loss"]))
    assert _gauge(engine, "train_remat_limit_percent") == 0
