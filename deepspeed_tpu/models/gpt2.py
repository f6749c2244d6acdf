"""GPT-2 model family (flax) — the flagship training model.

The reference trains GPT-2/Megatron-GPT through user-supplied torch modules
plus DeepSpeed's fused transformer kernel
(``csrc/transformer/ds_transformer_cuda.cpp``, wrapper
``deepspeed/ops/transformer/transformer.py:459``). Here the transformer block
is a flax module designed for the MXU: bf16 matmuls, fused-by-XLA
bias/gelu/layernorm epilogues, optional Pallas flash attention
(deepspeed_tpu.ops.flash_attention), ``jax.checkpoint`` for activation
rematerialization (analog of runtime/activation_checkpointing), and
Megatron-style tensor-parallel sharding expressed as PartitionSpecs
(``tp_specs``) instead of module surgery (module_inject/replace_module.py).

Sizes follow the GPT-2/GPT-3 ladder used by the reference benchmarks
(BASELINE.json configs: 125M…1.3B).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import DATA_AXES  # noqa: F401
from deepspeed_tpu.comm.mesh import seq_axis_active as _seq_axis_active
from deepspeed_tpu.ops.int8_training import (lm_logits,
                                              maybe_switchback)
from deepspeed_tpu.utils.jit import instance_cached_jit
from deepspeed_tpu.utils.sharding import maybe_constrain as _maybe_constrain


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash_attention: bool = True
    # flash tile-size override (0 = kernel default 256): the long-context
    # block-size A/B knob
    flash_block: int = 0
    # sequence/context parallelism over the seq mesh axis (capability
    # beyond the reference — SURVEY §5.7); requires dropout == 0 in the
    # attention core. sp_mode: "ring" (ppermute K/V ring, O(T/sp) memory)
    # or "ulysses" (all-to-all head scatter, needs n_head % sp == 0)
    sequence_parallel: bool = False
    sp_mode: str = "ring"
    # pad vocab to a multiple of 128 (lane width) for MXU efficiency;
    # Megatron does the same for TP divisibility.
    vocab_pad_multiple: int = 128
    # ZeRO-3 offload_param cooperation: params live in TPU-host memory
    # (engine places them; stage3.py:448) and every block fetches its own
    # weights into HBM *inside* its remat region — backward re-fetches, so
    # HBM holds only a few layers of weights at a time.
    offload_params: bool = False
    # MoE FFN (reference Megatron-MoE training recipe: deepspeed/moe/layer
    # dropped into the FFN slot). num_experts > 0 turns the layers in
    # ``moe_layers`` (None → every OTHER layer starting at 1, the
    # Megatron-Deepspeed expert_interval=2 default) into expert-parallel
    # MoE blocks; experts shard over the data/fsdp axes via MoE.tp_specs.
    # The model's ``__call__``/``loss_fn`` fold the gate load-balancing
    # loss in with weight ``moe_aux_weight``.
    num_experts: int = 0
    moe_layers: Optional[tuple] = None
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # SwitchBack int8 training (ops/int8_training.py): the projection
    # GEMMs (fwd + dx) run int8 x int8 on the MXU at twice the bf16 rate;
    # dw stays full precision. Experimental, opt-in; composes with
    # ZeRO/offload unchanged (params stay bf16).
    int8_training: bool = False

    def __post_init__(self):
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_mode must be 'ring' or 'ulysses', got "
                f"{self.sp_mode!r}")
        if self.num_experts > 0 and self.offload_params:
            raise ValueError(
                "num_experts > 0 with offload_params is unsupported: the "
                "in-step fetch table shares one block structure across "
                "layers, and MoE layers have a different param tree than "
                "dense ones")
        if self.num_experts > 0:
            layers = self.moe_layer_set
            if not layers:
                raise ValueError(
                    "num_experts > 0 needs at least one MoE layer "
                    "(moe_layers is empty)")
            bad = sorted(i for i in layers if not 0 <= i < self.n_layer)
            if bad:
                raise ValueError(
                    f"moe_layers {bad} out of range for n_layer="
                    f"{self.n_layer}")

    @property
    def moe_layer_set(self) -> frozenset:
        if self.num_experts <= 0:
            return frozenset()
        if self.moe_layers is not None:
            return frozenset(self.moe_layers)
        return frozenset(range(1, self.n_layer, 2))

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m


PRESETS: Dict[str, dict] = {
    "gpt2-125m": dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-350m": dict(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-760m": dict(n_embd=1536, n_layer=24, n_head=16),
    "gpt2-1.3b": dict(n_embd=2048, n_layer=24, n_head=16),
    "gpt2-2.7b": dict(n_embd=2560, n_layer=32, n_head=32),
    "gpt2-6.7b": dict(n_embd=4096, n_layer=32, n_head=32),
}


def config_for(name: str, **overrides) -> GPT2Config:
    if name not in PRESETS:
        raise ValueError(f"unknown preset {name!r}: {sorted(PRESETS)}")
    return GPT2Config(**{**PRESETS[name], **overrides})


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        B, T, C = x.shape
        H = cfg.n_head
        qkv = nn.Dense(3 * C, dtype=cfg.dtype, name="c_attn",
                       dot_general=maybe_switchback(cfg.int8_training))(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, C // H)
        k = k.reshape(B, T, H, C // H)
        v = v.reshape(B, T, H, C // H)

        if cfg.sequence_parallel and _seq_axis_active():
            from deepspeed_tpu.comm.mesh import get_global_mesh
            if cfg.sp_mode == "ulysses":
                # all-to-all SP (DeepSpeed-Ulysses): full-seq attention
                # over head subsets; needs n_head % sp == 0
                from deepspeed_tpu.ops.ulysses_attention import (
                    ulysses_self_attention)
                y = ulysses_self_attention(q, k, v, get_global_mesh(),
                                           block=cfg.flash_block)
            else:
                from deepspeed_tpu.ops.ring_attention import (
                    ring_self_attention)
                y = ring_self_attention(q, k, v, get_global_mesh())
        elif cfg.use_flash_attention:
            from deepspeed_tpu.ops.attention import causal_attention
            # the serving model's scope names (docs/observability.md
            # "Spans"); flax's own module names give the rest of the path
            with jax.named_scope("attn_kernel"):
                y = causal_attention(q, k, v, block_q=cfg.flash_block,
                                     block_k=cfg.flash_block)
        else:
            scale = 1.0 / jnp.sqrt(C // H).astype(cfg.dtype)
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            mask = jnp.tril(jnp.ones((T, T), bool))
            att = jnp.where(mask[None, None], att, jnp.finfo(att.dtype).min)
            att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(cfg.dtype)
            if cfg.dropout > 0.0 and not deterministic:
                att = nn.Dropout(cfg.dropout)(att, deterministic=False)
            y = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        y = y.reshape(B, T, C)
        y = nn.Dense(C, dtype=cfg.dtype, name="c_proj",
                     dot_general=maybe_switchback(cfg.int8_training))(y)
        if cfg.dropout > 0.0 and not deterministic:
            y = nn.Dropout(cfg.dropout)(y, deterministic=False)
        return y


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        C = x.shape[-1]
        h = nn.Dense(4 * C, dtype=cfg.dtype, name="c_fc",
                     dot_general=maybe_switchback(cfg.int8_training))(x)
        h = jax.nn.gelu(h, approximate=True)
        h = nn.Dense(C, dtype=cfg.dtype, name="c_proj",
                     dot_general=maybe_switchback(cfg.int8_training))(h)
        if cfg.dropout > 0.0 and not deterministic:
            h = nn.Dropout(cfg.dropout)(h, deterministic=False)
        return h


class Block(nn.Module):
    """Transformer block. With ``moe=True`` the FFN slot holds an
    expert-parallel MoE (reference deepspeed/moe/layer.py inside a
    Megatron-MoE GPT layer) and ``__call__`` returns ``(x, l_aux)`` — the
    gate's load-balancing loss rides out as a scalar so remat never needs
    a mutable collection. One class for both so the LN/attention/residual
    structure cannot drift between dense and MoE models."""
    config: GPT2Config
    moe: bool = False

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        # LayerNorm in fp32 for stability, output cast back (the reference's
        # fused kernels keep LN accumulation in fp32 too: normalize_kernels.cu)
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln_1")(x)
        x = x + CausalSelfAttention(cfg, name="attn")(h, deterministic)
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x)
        if self.moe:
            from deepspeed_tpu.moe.layer import MoE
            B, T, C = x.shape
            # tokens flatten to one group; the expert dispatch reshard
            # over the EP axes (= data/fsdp) IS the all-to-all
            y, l_aux, _ = MoE(hidden_size=C, num_experts=cfg.num_experts,
                              k=cfg.moe_top_k,
                              capacity_factor=cfg.moe_capacity_factor,
                              eval_capacity_factor=cfg.moe_capacity_factor,
                              min_capacity=4, dtype=cfg.dtype,
                              int8_training=cfg.int8_training,
                              name="moe")(h.reshape(B * T, C),
                                          train=not deterministic)
            return x + y.reshape(B, T, C), l_aux
        x = x + MLP(cfg, name="mlp")(h, deterministic)
        return x


def _fetch_to_device(tree, role: str, table: Optional[Dict[str, Any]]):
    """Host-memory param subtree → HBM (offload_param in-step fetch).

    ``table`` is the owning :class:`GPT2LMModel`'s fetch table (instance
    state, filled in by the engine via ``set_param_fetch_shardings`` —
    role → NamedSharding subtree with memory_kind='device'). Explicit
    NamedShardings are required under SPMD: a bare memory-space transfer
    leaves the partitioner's placement annotation unsharded and it rejects
    the program. Identity when no engine installed shardings (standalone
    use, eager-staging engines, non-TPU backends) and for concrete
    (non-traced) values: the fetch only makes sense inside the compiled
    step — during eager ``model.init`` a device_put would commit fresh
    params to one device."""
    if table is None or not table.get("active", False):
        return tree
    sh = table.get(role)
    if sh is None:
        return tree

    def put(x, s):
        if not isinstance(x, jax.core.Tracer):
            return x
        return jax.device_put(x, s)

    # flax hands the block subtree in as a FrozenDict while the engine's
    # sharding subtree is a plain dict — isomorphic but not tree_map
    # compatible. Both flatten in sorted-key order, so zip by leaf.
    leaves, treedef = jax.tree.flatten(tree)
    sh_leaves = jax.tree.leaves(sh)
    if len(sh_leaves) != len(leaves):
        raise ValueError(
            f"offload_param fetch shardings for role {role!r} have "
            f"{len(sh_leaves)} leaves, params have {len(leaves)}")
    return jax.tree.unflatten(
        treedef, [put(x, s) for x, s in zip(leaves, sh_leaves)])


class GPT2(nn.Module):
    """Causal LM. ``__call__`` returns logits; ``loss`` the mean CE loss."""
    config: GPT2Config
    # offload_param fetch table owned by the GPT2LMModel wrapper (mutable
    # dict shared by reference; per-model so two engines cannot clobber
    # each other's placements)
    fetch_table: Optional[Dict[str, Any]] = None

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True):
        cfg = self.config
        B, T = input_ids.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.padded_vocab_size, cfg.n_embd), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.n_positions, cfg.n_embd), jnp.float32)
        if cfg.offload_params:
            wte = _fetch_to_device(wte, "wte", self.fetch_table)
            wpe = _fetch_to_device(wpe, "wpe", self.fetch_table)
        # gather rows THEN cast (16 MB vs casting the whole fp32 table to
        # a 100+ MB bf16 copy per step), and slice positions statically
        with jax.named_scope("embed"):
            x = wte[input_ids].astype(cfg.dtype) + \
                wpe[:T].astype(cfg.dtype)[None]
        x = _maybe_constrain(x, P(DATA_AXES, "seq", None))
        if cfg.dropout > 0.0 and not deterministic:
            x = nn.Dropout(cfg.dropout)(x, deterministic=False)

        block = Block
        if cfg.offload_params:
            # the fetch sits INSIDE the remat region below, so backward
            # re-fetches this block's weights instead of pinning them in
            # HBM across the whole fwd+bwd (coordinator-prefetch analog —
            # XLA's scheduler overlaps the DMA with neighbouring compute)
            block = nn.map_variables(
                block, "params",
                trans_in_fn=lambda t: _fetch_to_device(
                    t, "block", self.fetch_table),
                trans_out_fn=lambda t: t, mutable=True, init=True)
        moe_set = cfg.moe_layer_set
        if cfg.remat:
            # dots-saveable + the flash kernel's tagged output: the policy
            # cannot see through the kernel's custom_vjp, so without the
            # name the flash forward re-runs in backward (ops/attention.py)
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_attn_out"))
            # deterministic is control flow (dropout gate, MoE train-mode
            # capacity), not data — keep it static under the remat trace
            # (argnum 2: flax counts the module instance as 0)
            block = nn.remat(block, prevent_cse=False, policy=policy,
                             static_argnums=(2,))
        l_aux_total = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layer):
            if i in moe_set:
                x, l_aux = block(cfg, moe=True,
                                 name=f"h_{i}")(x, deterministic)
                l_aux_total = l_aux_total + l_aux.astype(jnp.float32)
            else:
                x = block(cfg, name=f"h_{i}")(x, deterministic)

        ln_f = nn.LayerNorm
        if cfg.offload_params:
            ln_f = nn.map_variables(
                ln_f, "params",
                trans_in_fn=lambda t: _fetch_to_device(
                    t, "ln_f", self.fetch_table),
                trans_out_fn=lambda t: t, mutable=True, init=True)
        x = ln_f(dtype=cfg.dtype, name="ln_f")(x)
        with jax.named_scope("lm_head"):
            logits = lm_logits(x, wte.astype(cfg.dtype), cfg.int8_training)
        if moe_set:
            return logits, l_aux_total
        return logits


class GPT2LMModel:
    """Engine-facing wrapper: init + loss_fn + tp_specs.

    ``loss_fn(params, batch, rng)`` — batch is ``{"input_ids": [B,T] int32}``
    (next-token prediction) or ``{"input_ids", "labels"}``.
    """

    def __init__(self, config: GPT2Config):
        self.config = config
        self._fetch_table: Dict[str, Any] = {"active": False}
        self.module = GPT2(config, fetch_table=self._fetch_table)

    @property
    def handles_param_offload(self) -> bool:
        """Engine hint: with ``offload_params`` the model performs its own
        per-layer HBM fetches, so the engine must not coarse-fetch the
        whole tree at step start."""
        return self.config.offload_params

    def set_param_fetch_shardings(self, device_shardings) -> None:
        """Engine-provided device placements for the in-step fetches (the
        ZeRO policy's param shardings with memory_kind='device'). All
        blocks share one structure, so h_0's subtree serves every layer.
        ``None`` deactivates the in-jit fetches (engine stages eagerly)."""
        if device_shardings is None:
            self._fetch_table["active"] = False
            return
        self._fetch_table["active"] = True
        self._fetch_table["wte"] = device_shardings["wte"]
        self._fetch_table["wpe"] = device_shardings["wpe"]
        self._fetch_table["ln_f"] = device_shardings["ln_f"]
        if "h_0" in device_shardings:
            self._fetch_table["block"] = device_shardings["h_0"]

    def init(self, rng, example_batch=None, batch_size: int = 2,
             seq_len: Optional[int] = None):
        seq_len = seq_len or min(self.config.n_positions, 128)
        if example_batch is not None:
            ids = example_batch["input_ids"]
        else:
            ids = jnp.zeros((batch_size, seq_len), jnp.int32)
        # offload fetches are step-time only; flax jits init internally,
        # so without this guard the fetch would commit fresh params to one
        # device before the engine shards them
        prev = self._fetch_table.get("active", False)
        self._fetch_table["active"] = False
        try:
            # one compiled executable, wrapper cached on the instance:
            # params materialize device-side in a single execution
            # instead of per-op dispatch round trips (utils/jit.py)
            variables = instance_cached_jit(self, self.module.init)(
                rng, ids)
        finally:
            self._fetch_table["active"] = prev
        return variables["params"]

    def apply(self, params, input_ids, deterministic=True, rngs=None):
        """Returns logits; with MoE layers, ``(logits, l_aux_total)``."""
        return self.module.apply({"params": params}, input_ids,
                                 deterministic=deterministic, rngs=rngs)

    def loss_fn(self, params, batch, rng=None):
        cfg = self.config
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        rngs = {}
        if rng is not None and cfg.dropout > 0.0:
            rngs["dropout"] = rng
        if rng is not None and cfg.num_experts > 0:
            # gate randomness (rts noise / top-2 second-expert sampling)
            rngs["gating"] = jax.random.fold_in(rng, 1)
        rngs = rngs or None
        out = self.apply(params, input_ids,
                         deterministic=rng is None, rngs=rngs)
        l_aux = None
        if cfg.num_experts > 0:
            logits, l_aux = out
        else:
            logits = out
        if labels is None:
            labels = input_ids[:, 1:]
            logits = logits[:, :-1]
        logits = logits.astype(jnp.float32)
        # lse - gold instead of log_softmax: avoids materializing a full
        # fp32 [B, T, V] log-prob tensor (reductions only — at 350m/seq
        # 1024 that tensor is ~0.8 GB of HBM write+read per step)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        nll = lse - gold
        mask = (labels >= 0) & (labels < self.config.vocab_size)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
        if l_aux is not None:
            loss = loss + cfg.moe_aux_weight * l_aux
        return loss

    def tp_specs(self):
        """Megatron-style tensor-parallel placement: attention qkv + mlp up
        are column-parallel, the projections row-parallel, embeddings
        vocab-parallel (module_inject/layers.py:9-61 semantics, as sharding
        specs instead of module replacement)."""
        cfg = self.config
        block = {
            "ln_1": {"scale": P(), "bias": P()},
            "ln_2": {"scale": P(), "bias": P()},
            "attn": {
                "c_attn": {"kernel": P(None, "tensor"), "bias": P("tensor")},
                "c_proj": {"kernel": P("tensor", None), "bias": P()},
            },
            "mlp": {
                "c_fc": {"kernel": P(None, "tensor"), "bias": P("tensor")},
                "c_proj": {"kernel": P("tensor", None), "bias": P()},
            },
        }
        specs = {"wte": P("tensor", None), "wpe": P(),
                 "ln_f": {"scale": P(), "bias": P()}}
        moe_set = cfg.moe_layer_set
        if moe_set:
            from deepspeed_tpu.moe.layer import MoE
            moe_block = dict(block)
            del moe_block["mlp"]
            moe_block["moe"] = MoE.tp_specs()
        for i in range(cfg.n_layer):
            specs[f"h_{i}"] = moe_block if i in moe_set else block
        return specs

    def param_count(self, params) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))

    def flops_per_token(self) -> float:
        """~6 * N_active_params per token (training fwd+bwd). MoE layers
        count attention + top_k expert FFNs — the ACTIVE compute, not the
        parameter count (standard MoE throughput accounting)."""
        cfg = self.config
        n_moe = len(cfg.moe_layer_set)
        dense_ffn = 8 * cfg.n_embd ** 2
        n = (cfg.padded_vocab_size * cfg.n_embd
             + cfg.n_positions * cfg.n_embd
             + cfg.n_layer * (4 * cfg.n_embd ** 2)            # attention
             + (cfg.n_layer - n_moe) * dense_ffn              # dense FFN
             + n_moe * cfg.moe_top_k * dense_ffn)             # active experts
        return 6.0 * n
