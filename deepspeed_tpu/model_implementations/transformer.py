"""One fused inference transformer, many architectures.

The reference ships a C++ fused block (``DeepSpeedTransformerInference``,
``model_implementations/transformers/ds_transformer.py:17``) whose ~40 CUDA
ops (``csrc/transformer/inference/csrc/pt_binding.cpp:1701-1777``) are
specialised per policy (rotary for GPT-J/NeoX, ALiBi for BLOOM, pre/post-LN,
parallel residual). Here the whole block is functional JAX: XLA fuses the
bias/activation/residual epilogues into the MXU matmuls (the reason the
reference needed ``fused_gemm_gelu``/``residual_add_bias`` by hand), the
decode hot path uses the Pallas decode-attention kernel
(ops/pallas/decode_attention.py = ``softmax_context``), and prefill uses the
Pallas flash-attention kernel.

Tensor parallelism: weights carry Megatron-style PartitionSpecs
(:func:`tp_param_specs`) — column-parallel QKV/wi, row-parallel wo — and
GSPMD places the per-layer all-reduce the reference issues manually after
attn-out and mlp-out (``module_inject/layers.py:9`` LinearAllreduce).

Parameter schema (pytree of arrays)::

    wte [V, E]   wpe [P, E]?   ln_f {scale, bias}   lm_head [E, V]?
    layers: list of
      ln1 {scale, bias}   ln2 {scale, bias}?
      attn {wq, wk, wv [E, H, D], bq, bk, bv [H, D], wo [H, D, E], bo [E]}
      mlp  {wi [E, F], bi [F], wo [F, E], bo [E]}
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.inference.kv_cache import (KVCache, PagedKVCache, advance,
                                              append_token, paged_advance,
                                              paged_append_token,
                                              paged_gather_kv,
                                              paged_gather_slot_kv,
                                              paged_write_chunk,
                                              paged_write_prompt,
                                              paged_write_tokens, write_chunk,
                                              write_prompt)
from deepspeed_tpu.ops.pallas import decode_attention as _kernels
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.profiling.trace import scoped
from deepspeed_tpu.utils.sharding import map_kernel
from deepspeed_tpu.ops.int8_gemm import (maybe_int8_einsum,
                                         maybe_int8_matmul)

NEG_INF = -1e30


def model_family(cfg):
    """The module that runs ``cfg``'s model, or None for this module's
    own decoder. A configuration of another architecture names its
    module (``cfg.family``; it has ``init_params`` and this file's
    ``paged_prefill`` / ``paged_decode_step`` / ``causal_forward`` under
    the same contracts, and ``paged_prefill_chunk`` where a prompt chunk
    can attend its kind of pool), and the entry points below hand over
    to it; no model is imported here by name. The entry points such a
    module does not have (a dense cache, a draft chunk, a batched
    verify; a prompt chunk, for a family without one) say so."""
    name = getattr(cfg, "family", None)
    return importlib.import_module(name) if name else None


def _own_decoder_only(cfg, what: str) -> None:
    if model_family(cfg) is not None:
        raise NotImplementedError(
            f"{what} is not implemented for a {type(cfg).__name__} "
            f"model ({cfg.family}): it is served through "
            "ContinuousBatchingServer (paged prefill, monolithic or by "
            "chunks where its module has paged_prefill_chunk, + paged "
            "decode) and scored through InferenceEngine.forward")


@dataclasses.dataclass(frozen=True)
class InferenceTransformerConfig:
    vocab_size: int
    n_positions: int
    n_embd: int
    n_layer: int
    n_head: int
    n_kv_head: Optional[int] = None          # != n_head → GQA/MQA
    intermediate_size: Optional[int] = None  # default 4*E
    pre_layer_norm: bool = True              # False → BERT-style post-LN
    positional: str = "learned"              # learned | rotary | alibi | none
    rotary_dim: int = 0                      # 0 → full head dim when rotary
    rotary_interleaved: bool = False         # True → GPT-J style pairs
    rotary_base: float = 10000.0
    parallel_attn_mlp: bool = False          # GPT-J / GPT-NeoX parallel block
    activation: str = "gelu_new"             # gelu | gelu_new | relu | silu
    norm_type: str = "layernorm"             # layernorm | rmsnorm (LLaMA)
    gated_mlp: bool = False                  # SwiGLU: wg gate projection
    # KV cache S dim sharded over the mesh `seq` axis: the decode
    # attention must take the XLA path (GSPMD partitions its softmax;
    # the Pallas kernel is single-shard)
    seq_shard_kv: bool = False
    layer_norm_eps: float = 1e-5
    tied_lm_head: bool = True
    attn_scale: Optional[float] = None       # default 1/sqrt(head_dim)
    # ALiBi slope multiplier: BLOOM adds the bias UNscaled (baddbmm
    # beta=1); Falcon scales (scores + alibi) by 1/sqrt(D) together, so
    # its effective slopes carry the attn scale — FalconPolicy sets this
    alibi_scale: float = 1.0
    # per-layer sliding-window size (None = global) — GPT-Neo alternates
    # global/local(256); length n_layer when set
    local_windows: Optional[tuple] = None
    # w8a8: run the MLP in/out GEMMs as int8 x int8 -> int32 on the MXU
    # when weights are stored int8 (ops/int8_gemm.py). Attention
    # projections keep the dequant-bf16 path (non-foldable scale grid);
    # the tied LM head is the embedding table (never quantized).
    int8_compute: bool = False
    # MoE FFN (reference ops/transformer/inference/moe_inference.py):
    # layers in ``moe_layers`` replace their MLP with num_experts experts
    # behind a top-k gate; experts shard over the ``expert`` mesh axis
    num_experts: int = 0
    moe_layers: Optional[tuple] = None       # None + num_experts>0 → all
    moe_top_k: int = 1                       # inference default: top-1
    # renormalize the selected top-k gate probs to sum to 1 (HF-Mixtral
    # semantics, and what reference top2gating's denom does). False →
    # GShard top-1 semantics (expert output scaled by its raw softmax
    # prob) — what models trained with top1_gating expect when served.
    moe_renormalize: bool = True
    # expert FFN activation when it differs from the dense MLP's (some
    # imported checkpoints mix activations across the FFN slots).
    # None → cfg.activation.
    moe_activation: Optional[str] = None
    # "lm" → project to vocab logits; "none" → return final hidden states
    # (CLIP text encoder: causal pre-LN trunk with no LM head)
    head: str = "lm"
    # head_dim when it is NOT n_embd // n_head (Gemma-7b: 256-dim heads
    # on a 3072/16 trunk — projections are [E, H*256])
    explicit_head_dim: Optional[int] = None
    # input-embedding multiplier (Gemma: sqrt(n_embd), applied to the
    # embedding only — the tied LM head reads the RAW table)
    embed_scale: float = 1.0
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.explicit_head_dim or self.n_embd // self.n_head

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def ffn(self) -> int:
        return self.intermediate_size or 4 * self.n_embd

    def is_moe_layer(self, idx: int) -> bool:
        if self.num_experts <= 0:
            return False
        return self.moe_layers is None or idx in self.moe_layers

    @property
    def scale(self) -> float:
        return self.attn_scale if self.attn_scale is not None else (
            1.0 / math.sqrt(self.head_dim))


# ---------------------------------------------------------------- params

def init_params(rng: jax.Array, cfg: InferenceTransformerConfig) -> Dict:
    """Random init (tests / set_empty_params); policies overwrite with HF
    weights (module_inject analog, deepspeed_tpu/module_inject/).

    Jitted wholesale: one device-side executable instead of one compile
    and dispatch per tensor — material at serving-scale layer counts
    (see models/gpt2.py init)."""
    return _jit_init_for(cfg)(rng)


@functools.lru_cache(maxsize=None)
def _jit_init_for(cfg: InferenceTransformerConfig):
    # one jit wrapper per (frozen, hashable) config: repeated inits of the
    # same geometry reuse the traced executable instead of re-compiling
    return jax.jit(lambda r: _init_params_impl(r, cfg))


def _init_params_impl(rng: jax.Array, cfg: InferenceTransformerConfig) -> Dict:
    E, H, D, F = cfg.n_embd, cfg.n_head, cfg.head_dim, cfg.ffn
    KH = cfg.kv_heads
    keys = iter(jax.random.split(rng, 4 + 8 * cfg.n_layer))
    dt = cfg.dtype

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def norm():
        p = {"scale": jnp.ones((E,), dt)}
        if cfg.norm_type != "rmsnorm":   # RMSNorm has no bias (see
            p["bias"] = jnp.zeros((E,), dt)   # _layer_norm dispatch)
        return p

    params: Dict[str, Any] = {
        "wte": dense(next(keys), (cfg.vocab_size, E), E),
        "ln_f": norm(),
        "layers": [],
    }
    if cfg.positional == "learned":
        params["wpe"] = dense(next(keys), (cfg.n_positions, E), E)
    if not cfg.tied_lm_head:
        params["lm_head"] = dense(next(keys), (E, cfg.vocab_size), E)
    for _ in range(cfg.n_layer):
        layer = {
            "ln1": norm(),
            "attn": {
                "wq": dense(next(keys), (E, H, D), E),
                "wk": dense(next(keys), (E, KH, D), E),
                "wv": dense(next(keys), (E, KH, D), E),
                "bq": jnp.zeros((H, D), dt),
                "bk": jnp.zeros((KH, D), dt),
                "bv": jnp.zeros((KH, D), dt),
                "wo": dense(next(keys), (H, D, E), E),
                "bo": jnp.zeros((E,), dt),
            },
            "mlp": {
                "wi": dense(next(keys), (E, F), E),
                "bi": jnp.zeros((F,), dt),
                "wo": dense(next(keys), (F, E), F),
                "bo": jnp.zeros((E,), dt),
            },
        }
        if cfg.gated_mlp:
            layer["mlp"]["wg"] = dense(next(keys), (E, F), E)
        if not (cfg.parallel_attn_mlp and cfg.pre_layer_norm
                and cfg.positional == "rotary" and cfg.rotary_interleaved):
            layer["ln2"] = norm()
        params["layers"].append(layer)
    # MoE layers replace their MLP with a gate + stacked experts; with
    # gated_mlp the experts are SwiGLU (Mixtral layout: wg/wi/wo, no
    # biases) instead of the reference's two-matrix FFN
    for i, layer in enumerate(params["layers"]):
        if cfg.is_moe_layer(i):
            X = cfg.num_experts
            k = jax.random.fold_in(rng, 1000 + i)
            ks = jax.random.split(k, 4)
            del layer["mlp"]
            experts = {"wi": dense(ks[1], (X, E, F), E),
                       "wo": dense(ks[2], (X, F, E), F)}
            if cfg.gated_mlp:
                experts["wg"] = dense(ks[3], (X, E, F), E)
            else:
                experts["bi"] = jnp.zeros((X, F), dt)
                experts["bo"] = jnp.zeros((X, E), dt)
            layer["moe"] = {"gate": dense(ks[0], (E, X), E),
                            "experts": experts}
    return params


def tp_param_specs(params: Dict) -> Dict:
    """Megatron TP sharding for the param tree over the ``tensor`` mesh axis.

    Column-parallel: wq/wk/wv (head dim), mlp.wi (ffn dim). Row-parallel:
    attn.wo (head dim), mlp.wo (ffn dim) — GSPMD inserts the psum the
    reference's LinearAllreduce does by hand. Embeddings/LN replicated
    (matches reference AutoTP scope)."""
    def spec_for(path: str) -> P:
        # int8 leaves: the q payload shards like the weight it replaces;
        # the per-dim0-group scale [d0, 1, ...] follows the weight's dim-0
        # sharding (so a row-parallel weight keeps its scales local)
        if path.endswith(".q"):
            return spec_for(path[:-2])
        if path.endswith(".scale"):
            # quant scales are [*leading dims, 1]: follow the weight's
            # leading-dim sharding. LayerNorm .scale paths recurse to P()
            # and come out replicated, which is already correct for them.
            base = tuple(spec_for(path[:-len(".scale")]))
            return P(*base[:-1], None) if base else P()
        if path.endswith(".oscale"):
            # per-output-channel scales (quantize_weight_out): size-1 on
            # contraction dims, weight extent on output dims — follow the
            # weight's OUTPUT sharding; row-parallel weights shard a
            # contraction dim, so their scales replicate (the post-psum
            # rescale is global)
            wpath = path[: -len(".oscale")]
            base = list(spec_for(wpath))
            if wpath.endswith(("attn.wo", "mlp.wo")):
                base = [None] * len(base)
            elif wpath.endswith("experts.wo"):
                base = ["expert", None, None]
            return P(*base)
        if path.endswith(("attn.wq", "attn.wk", "attn.wv")):
            return P(None, "tensor", None)
        if path.endswith(("attn.bq", "attn.bk", "attn.bv")):
            return P("tensor", None)
        if path.endswith("attn.wo"):
            return P("tensor", None, None)
        if path.endswith(("mlp.wi", "mlp.wg")):   # wg: SwiGLU gate, same
            return P(None, "tensor")              # column-parallel split
        if path.endswith(("mlp.bi", "mlp.bg")):
            return P("tensor")
        if path.endswith("mlp.wo"):
            return P("tensor", None)
        # MoE experts: expert-parallel over dim 0, Megatron TP within
        # (reference moe_inference.py EP groups + per-expert TP slicing)
        if path.endswith(("experts.wi", "experts.wg")):
            return P("expert", None, "tensor")
        if path.endswith("experts.bi"):
            return P("expert", "tensor")
        if path.endswith("experts.wo"):
            return P("expert", "tensor", None)
        if path.endswith("experts.bo"):
            return P("expert", None)
        return P()

    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}.{k}" if path else k)
                    for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, path) for v in tree]
        return spec_for(path)

    return walk(params)


# ---------------------------------------------------------------- math

def _w(w, dtype):
    """Resolve a weight leaf that may be stored as TRUE int8: a dict
    ``{"q": int8 [orig shape], "scale": f32 [d0, 1, ...]}`` with per-group
    scales along dim 0 (module_inject/quantize.py GroupQuantizer). The
    dequant multiply fuses into the consuming matmul under XLA, so HBM
    holds int8 + scales only (the reference stores int8 + per-group scales
    the same way, replace_module.py:140-199)."""
    if isinstance(w, dict) and "q" in w:
        # lazy import: module_inject's package init reaches back into this
        # module via the policy table, so a top-level import would cycle
        from deepspeed_tpu.module_inject.quantize import dequantize_weight
        return dequantize_weight(w, dtype)
    return w.astype(dtype) if w.dtype != dtype else w


@scoped("ln")
def _layer_norm(x, p, eps):
    """LayerNorm, or RMSNorm when the param dict carries no bias (the
    LLaMA family: no centering, scale only) — data-driven so every call
    site serves both."""
    xf = x.astype(jnp.float32)
    if "bias" not in p:
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _act(x, kind):
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if kind == "quick_gelu":                 # CLIP: x * sigmoid(1.702 x)
        return x * jax.nn.sigmoid(1.702 * x)
    if kind in ("silu", "swish"):            # LLaMA/Mistral gate act
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=True)  # gelu_new / gelu_fast


def _rotary_angles(positions, dim, base):
    """positions [...]; returns cos/sin [..., dim//2] in fp32."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, jnp.float32) / dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, positions, rotary_dim, base, interleaved):
    """x [..., D] with leading position dims matching ``positions``.

    Analog of ``apply_rotary_pos_emb.cu`` (csrc/transformer/inference).
    ``interleaved=True`` is the GPT-J pairing (even/odd lanes); False is the
    NeoX half-split pairing.
    """
    D = x.shape[-1]
    rd = rotary_dim or D
    cos, sin = _rotary_angles(positions, rd, base)  # [..., rd/2]
    cos = jnp.expand_dims(cos, -2)  # broadcast over heads [..., 1, rd/2]
    sin = jnp.expand_dims(sin, -2)
    rot, rest = x[..., :rd].astype(jnp.float32), x[..., rd:]
    if interleaved:
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
    else:
        half = rd // 2
        x1, x2 = rot[..., :half], rot[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), rest], -1)


def alibi_slopes(n_head: int) -> jnp.ndarray:
    """BLOOM ALiBi head slopes (fp32 [H])."""
    def pow2slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]
    if math.log2(n_head).is_integer():
        s = pow2slopes(n_head)
    else:
        closest = 2 ** math.floor(math.log2(n_head))
        s = pow2slopes(closest)
        extra = pow2slopes(2 * closest)
        s += extra[0::2][: n_head - closest]
    return jnp.asarray(s, jnp.float32)


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=-2)


def _head_axis(mesh, H: int, KH: int):
    """The mesh axis a kernel's head dim is mapped over: ``tensor`` when
    the engine's mesh shards heads and both head counts divide (the
    Megatron layout of wq/wk/wv and of both KV caches), else none —
    the kernel then sees all heads on every device."""
    if mesh is None or "tensor" not in mesh.axis_names:
        return None
    tp = mesh.shape["tensor"]
    return "tensor" if tp > 1 and H % tp == KH % tp == 0 else None


def _use_decode_kernel(cfg: InferenceTransformerConfig, H: int, KH: int,
                       window) -> bool:
    """The Pallas decode family serves plain causal attention on TPU;
    ALiBi, windowed layers (``local_windows``: they keep their whole
    context in the block tables and not a ring, and the kernel's window
    is a ring's; ``model_implementations/laguna.py`` is the family whose
    window layers decode through the kernel), a seq-sharded KV cache and
    the CPU take the XLA formulation."""
    return (cfg.positional != "alibi" and window is None
            and jax.default_backend() == "tpu" and H % KH == 0
            and not cfg.seq_shard_kv)


@scoped("attn_kernel")
def _prefill_attention(q, k, v, cfg: InferenceTransformerConfig,
                       causal: bool = True, key_mask=None, window=None,
                       mesh=None):
    """Attention over a full sequence. q [B, T, H, D], k/v [B, T, KH, D]
    → [B, T, H, D]. ``key_mask [B, T]`` masks padded keys (encoder path);
    ``window`` is a sliding-window size (GPT-Neo local layers).

    Uses the Pallas flash kernel for the causal no-bias case, its
    windowed forward (``flash_attention_window_fwd``: K blocks outside a
    query block's windows skipped) for a ``local_windows`` layer. What
    still takes the ``[T, T]`` XLA einsum oracle: ALiBi, bidirectional
    and key-masked (encoder) attention, a prompt the kernel's blocks do
    not tile (T < 128 or T % 128), and every path off the TPU. The
    windowed kernel is forward only: this module serves, it does not
    train.
    """
    B, T, H, D = q.shape
    use_flash = (causal and key_mask is None
                 and cfg.positional != "alibi"
                 and jax.default_backend() == "tpu" and T >= 128 and
                 T % 128 == 0 and H % k.shape[2] == 0)
    if use_flash:
        # GQA stays unexpanded: the kernel streams each kv head once for
        # its whole query group (flash_attention HKV|H contract)
        spec = P(None, None, _head_axis(mesh, H, k.shape[2]), None)
        return map_kernel(
            functools.partial(flash_attention, causal=True,
                              scale=cfg.scale, window=window),
            mesh, (spec, spec, spec), spec)(q, k, v)
    k = _repeat_kv(k, H // k.shape[2])
    v = _repeat_kv(v, H // v.shape[2])
    # bf16 dot inputs, fp32 accumulation — an upfront fp32 cast would
    # quarter the MXU rate (same fix as the Pallas kernels)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                     preferred_element_type=jnp.float32) * cfg.scale
    if cfg.positional == "alibi":
        slopes = alibi_slopes(H) * cfg.alibi_scale
        # BLOOM bias: slope * (key_pos - query_pos) under causal mask
        rel = (jnp.arange(T)[None, :] - jnp.arange(T)[:, None])[None, None]
        att = att + slopes[None, :, None, None] * rel
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        if window is not None:  # HF GPT-Neo: query i sees keys in (i-w, i]
            mask &= (jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
                     < window)
        att = jnp.where(mask[None, None], att, NEG_INF)
    if key_mask is not None:
        att = jnp.where(key_mask[:, None, None, :].astype(bool), att,
                        NEG_INF)
    p = jax.nn.softmax(att, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@scoped("attn_kernel")
def _decode_attention(q, k_cache, v_cache, live,
                      cfg: InferenceTransformerConfig, window=None,
                      mesh=None):
    """One-token attention against the cache. q [B, H, D], cache
    [B, S, KH, D], ``live [B]`` = number of valid cache positions
    *including* the just-appended token → [B, H, D]. Pallas
    ``softmax_context`` analog on TPU, cache-layout- and GQA-native;
    XLA fallback for ALiBi / windowed / seq-sharded-KV / CPU."""
    B, H, D = q.shape
    KH = k_cache.shape[2]
    S = k_cache.shape[1]
    if _use_decode_kernel(cfg, H, KH, window):
        # cache-native + GQA-native kernel: no per-step cache transpose,
        # no _repeat_kv materialization — decode reads exactly the live
        # cache bytes once
        hs = _head_axis(mesh, H, KH)
        q_spec, kv_spec = P(None, hs, None), P(None, None, hs, None)
        return map_kernel(
            functools.partial(_kernels.decode_attention, scale=cfg.scale,
                              block_k=128),
            mesh, (q_spec, kv_spec, kv_spec, P()), q_spec)(
                q, k_cache, v_cache, live)
    s = jnp.einsum("bhd,bshd->bhs", q, _repeat_kv(k_cache, H // KH),
                   preferred_element_type=jnp.float32)
    s = s * cfg.scale
    pos = jnp.arange(S)[None, None, :]
    if cfg.positional == "alibi":
        slopes = alibi_slopes(H) * cfg.alibi_scale
        qpos = (live - 1)[:, None, None]  # query sits at the last live slot
        s = s + slopes[None, :, None] * (pos - qpos)
    s = jnp.where(pos < live[:, None, None], s, NEG_INF)
    if window is not None:
        s = jnp.where(pos > (live - 1 - window)[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p,
                      _repeat_kv(v_cache, H // KH).astype(jnp.float32)
                      ).astype(q.dtype)


def _paged_kernel(kernel, q, cache: PagedKVCache, layer_idx: int,
                  cfg: InferenceTransformerConfig, mesh, table, bound):
    """One call shape for the three paged Pallas kernels: ``q`` against
    layer ``layer_idx`` of the pool through ``table``, causal bound
    ``bound`` (live lengths / chunk start). The kernel's operand is the
    pool as it is stored, all layers of it: the layer is a block offset
    added to the table entries the kernel walks, so no K or V byte is
    copied between the pool and the call. An int8 pool adds its two scale tiles (empty for
    fp pools — the call, and therefore the traced signature, is
    unchanged). Under the engine's mesh the kernel is mapped over the
    ``tensor`` axis: each shard attends its own kv heads of the pool
    (the major part of the pool's lane dim), which is how the pool is
    laid out across devices."""
    hs = _head_axis(mesh, q.shape[-2], cache.num_kv_heads)
    q_spec = P(*[None] * (q.ndim - 2), hs, None)     # [..., H, D]
    pool = P(None, None, None, hs)                   # [L, NB, BS, KH*D]
    scales = ([] if cache.k_scale is None else
              [cache.k_scale, cache.v_scale])        # [L, NB, KH, BS]

    def call(q, k, v, table, bound, *sc):
        return kernel(q, k, v, table, bound, scale=cfg.scale,
                      layer=layer_idx,
                      **dict(zip(("k_scale", "v_scale"), sc)))
    with jax.named_scope("attn_kernel"):
        return map_kernel(
            call, mesh,
            (q_spec, pool, pool, P(), P(),
             *[P(None, None, hs, None)] * len(scales)),
            q_spec)(q, cache.k, cache.v, table, bound, *scales)


def _paged_decode_attention(q, cache: PagedKVCache, layer_idx: int,
                            cfg: InferenceTransformerConfig, live,
                            window=None, mesh=None):
    """One-token attention through the paged pool. q ``[S, H, D]``,
    ``live [S]`` = valid positions including the just-appended token.
    TPU fast path: the Pallas paged kernel gathers K/V blocks through the
    scalar-prefetched block table (no per-slot contiguous cache is ever
    materialized). Fallback: gather through the block table with XLA
    (:func:`paged_gather_kv`, ``[num_slots, max_context, H, D]`` a layer
    a step), then reuse :func:`_decode_attention` — gathered position j
    is logical position j, so the math (and every masked softmax bit) is
    identical to the dense-cache path. Exactly these cases fall back:
    every path off the TPU; ALiBi; a seq-sharded KV pool; and a
    ``local_windows`` layer (GPT-Neo), which keeps every block of its
    context in the tables although it reads ``window`` rows: the kernel's
    window walks a bounded ring (``paged_window_decode_attention``), and
    giving these layers rings is the Laguna family's pool
    (``PagedKVCache.layer_map``), not yet this decoder's."""
    if _use_decode_kernel(cfg, q.shape[1], cache.num_kv_heads, window):
        return _paged_kernel(_kernels.paged_decode_attention, q, cache,
                             layer_idx, cfg, mesh, cache.block_tables, live)
    k_cache, v_cache = paged_gather_kv(cache, layer_idx)
    return _decode_attention(q, k_cache, v_cache, live, cfg, window=window)


@scoped("attn_kernel")
def _chunk_attention(q, k_cache, v_cache, lengths,
                     cfg: InferenceTransformerConfig, window=None):
    """Speculative-verify attention: ``q [B, K, H, D]`` for K tokens at
    positions ``lengths[b]..lengths[b]+K-1``, against a cache that
    already holds the chunk's own k/v at those positions
    (:func:`deepspeed_tpu.inference.kv_cache.write_chunk`). Per-query
    causal bound: key position s is visible to chunk query i iff
    ``s < lengths[b] + i + 1``. K is small (the draft window), so the
    XLA einsum path is the right tool — no Pallas kernel needed."""
    B, K, H, D = q.shape
    KH = k_cache.shape[2]
    S = k_cache.shape[1]
    s = jnp.einsum("bkhd,bshd->bhks", q, _repeat_kv(k_cache, H // KH),
                   preferred_element_type=jnp.float32)
    s = s * cfg.scale
    pos = jnp.arange(S)[None, None, None, :]            # [1,1,1,S]
    qpos = (lengths[:, None] + jnp.arange(K)[None, :])  # [B,K]
    if cfg.positional == "alibi":
        slopes = alibi_slopes(H) * cfg.alibi_scale
        s = s + slopes[None, :, None, None] * (
            pos - qpos[:, None, :, None])
    live = (qpos + 1)[:, None, :, None]                 # [B,1,K,1]
    s = jnp.where(pos < live, s, NEG_INF)
    if window is not None:
        s = jnp.where(pos > (qpos[:, None, :, None] - window), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhks,bshd->bkhd", p,
                      _repeat_kv(v_cache, H // KH).astype(jnp.float32)
                      ).astype(q.dtype)


def _paged_verify_attention(q, cache: PagedKVCache, layer_idx: int,
                            cfg: InferenceTransformerConfig, window=None,
                            mesh=None):
    """Speculative-verify attention through the paged pool for ALL
    slots: ``q [S, K, H, D]`` — each slot's K-token candidate chunk at
    absolute positions ``lengths[s]..lengths[s]+K-1`` — attends that
    slot's resident context plus the chunk itself through its block
    table. TPU fast path: the Pallas batched-verify kernel streams pool
    blocks via the scalar-prefetched tables. Fallback (CPU / ALiBi / windowed): gather per-slot caches
    with XLA and reuse :func:`_chunk_attention` with per-slot
    ``lengths`` — the identical per-query causal bound, so the paged
    verify cannot diverge from the dense :func:`decode_chunk` math."""
    if _use_decode_kernel(cfg, q.shape[2], cache.num_kv_heads, window):
        return _paged_kernel(_kernels.paged_verify_attention, q, cache,
                             layer_idx, cfg, mesh, cache.block_tables,
                             cache.lengths)
    k_cache, v_cache = paged_gather_kv(cache, layer_idx)
    return _chunk_attention(q, k_cache, v_cache, cache.lengths, cfg,
                            window=window)


def _paged_chunk_attention(q, cache: PagedKVCache, layer_idx: int,
                           cfg: InferenceTransformerConfig, slot, start,
                           window=None, mesh=None):
    """Chunked-prefill attention through the paged pool: ``q [1, C, H,
    D]`` at absolute positions ``start..start+C-1`` attends the
    prefilling slot's already-resident prefix (earlier chunks and
    prefix-cache hits) plus the chunk itself, through the block table.
    TPU fast path: the Pallas chunk kernel streams pool blocks via the
    scalar-prefetched table. Fallback (CPU / ALiBi / windowed): gather
    ONE slot's cache with XLA and reuse :func:`_chunk_attention` with
    ``lengths = start`` — the identical per-query causal bound, so the
    chunked path cannot diverge from the verify/dense math."""
    if _use_decode_kernel(cfg, q.shape[2], cache.num_kv_heads, window):
        row = jax.lax.dynamic_slice_in_dim(cache.block_tables, slot, 1,
                                           0)[0]
        return _paged_kernel(_kernels.paged_chunk_attention, q[0], cache,
                             layer_idx, cfg, mesh, row, start)[None]
    k_cache, v_cache = paged_gather_slot_kv(cache, layer_idx, slot)
    return _chunk_attention(q, k_cache, v_cache,
                            jnp.reshape(start, (1,)).astype(jnp.int32),
                            cfg, window=window)


# ---------------------------------------------------------------- blocks

@scoped("attn_qkv")
def _qkv(x, a, cfg, positions):
    """x [..., E] → q [..., H, D], k/v [..., KH, D] with rotary applied."""
    dt = x.dtype
    proj = functools.partial(maybe_int8_einsum, "...e,ehd->...hd", x,
                             dtype=dt, int8_compute=cfg.int8_compute,
                             x_contract_ndim=1, w_out_ndim=2)
    q = proj(w=a["wq"]) + a["bq"]
    k = proj(w=a["wk"]) + a["bk"]
    v = proj(w=a["wv"]) + a["bv"]
    if cfg.positional == "rotary":
        q = apply_rotary(q, positions, cfg.rotary_dim, cfg.rotary_base,
                         cfg.rotary_interleaved)
        k = apply_rotary(k, positions, cfg.rotary_dim, cfg.rotary_base,
                         cfg.rotary_interleaved)
    return q, k, v


def _mlp(x, m, cfg):
    up = maybe_int8_matmul(x, m["wi"], x.dtype, cfg.int8_compute) + m["bi"]
    if "wg" in m:
        # gated MLP (LLaMA SwiGLU): down(act(gate(x)) * up(x))
        g = maybe_int8_matmul(x, m["wg"], x.dtype, cfg.int8_compute)
        if "bg" in m:
            g = g + m["bg"]
        gate = _act(g.astype(jnp.float32), cfg.activation)
        h = gate * up.astype(jnp.float32)
    else:
        h = _act(up.astype(jnp.float32), cfg.activation)
    return maybe_int8_matmul(h.astype(x.dtype), m["wo"], x.dtype,
                             cfg.int8_compute) + m["bo"]


def _moe_mlp(x, moe, cfg, mesh=None):
    """MoE FFN of the generic decoder (reference moe_inference.py: gate →
    einsum dispatch → all-to-all → expert FFN → all-to-all → combine).
    Which path does what:

    * THIS function dispatches densely over ``[X, S, ...]``: every expert
      runs on every token, masked. Only under a mesh with an ``expert``
      axis of more than one device does the sharding constraint on the
      expert dim make XLA lower the dispatch/combine einsums to the
      all-to-all pair the reference issues by hand (``einsum_sec_sm_ecm``
      + ``_AllToAll``, moe_inference.py:1-466). On one device, or on a
      mesh without that axis, it issues NO collective.
    * The held-experts layer (``longcat_flash.moe_layer``) never issues
      one: a process computes the experts it holds over the picks that
      landed on them (a grouped matmul) and leaves the picks on absent
      experts to their holders. It has no ``[X, S, E]`` tensor.

    Inference gating is exact top-k (no capacity drop: serving must not
    silently zero tokens the way capacity-bound training may)."""
    dt = x.dtype
    shape = x.shape
    t = x.reshape(-1, shape[-1])                         # [S, E]
    logits = (t @ _w(moe["gate"], dt)).astype(jnp.float32)   # [S, X]
    probs = jax.nn.softmax(logits, axis=-1)
    k = min(cfg.moe_top_k, cfg.num_experts)
    top_p, top_i = jax.lax.top_k(probs, k)               # [S, k]
    # renormalized combine weights over the selected experts (top-2 norm
    # matches sharded_moe.py's second-place renormalization); when
    # moe_renormalize=False keep the raw softmax probs (GShard top-1)
    if cfg.moe_renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    dispatch = jnp.sum(jax.nn.one_hot(top_i, cfg.num_experts, dtype=dt) *
                       top_p[..., None].astype(dt), axis=1)   # [S, X]
    sel = jnp.sum(jax.nn.one_hot(top_i, cfg.num_experts, dtype=dt),
                  axis=1)                                 # 0/1 [S, X]
    ex = moe["experts"]
    act = cfg.moe_activation or cfg.activation
    xin = jnp.einsum("sx,se->xse", sel, t)                # [X, S, E]
    xin = _maybe_expert_constrain(xin, mesh)
    up_proj = functools.partial(maybe_int8_einsum, "xse,xef->xsf", xin,
                                dtype=dt, int8_compute=cfg.int8_compute,
                                x_contract_ndim=1, w_out_ndim=1)
    if "wg" in ex:
        # gated (Mixtral) experts: down(act(gate(x)) * up(x)), no biases
        g = up_proj(w=ex["wg"])
        u = up_proj(w=ex["wi"])
        h = (_act(g, act) * u).astype(dt)
        out = maybe_int8_einsum("xsf,xfe->xse", h, ex["wo"], dt,
                                cfg.int8_compute, 1, 1)
    else:
        h = _act(up_proj(w=ex["wi"]) + ex["bi"][:, None, :],
                 act).astype(dt)
        out = maybe_int8_einsum("xsf,xfe->xse", h, ex["wo"], dt,
                                cfg.int8_compute, 1, 1) + \
            ex["bo"][:, None, :]
    out = _maybe_expert_constrain(out, mesh)
    combined = jnp.einsum("sx,xse->se", dispatch, out)    # combine
    return combined.reshape(shape)


def _maybe_expert_constrain(t, mesh):
    """Pin the leading expert dim to the ``expert`` mesh axis when one is
    live — this is what turns dispatch/combine into EP all-to-alls. The
    mesh is the CALLER's (the inference engine's own EP×TP mesh, threaded
    through the forward entry points; falls back to the training global
    mesh so shard_map-free training setups compose)."""
    if mesh is None:
        from deepspeed_tpu.comm.mesh import get_global_mesh, has_global_mesh
        mesh = get_global_mesh() if has_global_mesh() else None
    if (mesh is not None and "expert" in mesh.axis_names and
            mesh.shape["expert"] > 1):
        return jax.lax.with_sharding_constraint(
            t, jax.sharding.NamedSharding(
                mesh, P("expert", *([None] * (t.ndim - 1)))))
    return t


@scoped("attn_out")
def _attn_out(attn, a, spec: str, dtype, cfg):
    """The attention output projection (``spec``: the call site's
    einsum, ``...hd,hde->...e`` or its one-token form)."""
    return maybe_int8_einsum(spec, attn, a["wo"], dtype, cfg.int8_compute,
                             2, 1) + a["bo"]


@scoped("mlp")
def _ffn(x, layer, cfg, mesh=None):
    """MLP or MoE, by layer schema."""
    if "moe" in layer:
        return _moe_mlp(x, layer["moe"], cfg, mesh)
    return _mlp(x, layer["mlp"], cfg)


def _post_attn(x, ln1_out, attn_out, layer, cfg, mesh=None):
    """Shared residual/LN trident after attention (parallel-attn-mlp /
    pre-LN / post-LN) — ONE definition for _block_seq, _block_decode and
    _block_chunk so the prefill, decode and verify paths cannot
    diverge."""
    if cfg.parallel_attn_mlp:
        # GPT-J/NeoX: x + attn(ln1(x)) + mlp(ln(x)); GPT-J shares ln1
        ln2 = layer.get("ln2")
        mlp_in = (_layer_norm(x, ln2, cfg.layer_norm_eps)
                  if ln2 is not None else ln1_out)
        return x + attn_out + _ffn(mlp_in, layer, cfg, mesh)
    if cfg.pre_layer_norm:
        x = x + attn_out
        return x + _ffn(_layer_norm(x, layer["ln2"], cfg.layer_norm_eps),
                        layer, cfg, mesh)
    x = _layer_norm(x + attn_out, layer["ln1"], cfg.layer_norm_eps)
    return _layer_norm(x + _ffn(x, layer, cfg, mesh), layer["ln2"],
                       cfg.layer_norm_eps)


def _block_seq(x, layer, cfg, positions, lengths, cache, layer_idx,
               causal=True, key_mask=None, mesh=None, slot=None):
    """Full-sequence block (prefill / encoder). x [B, T, E]. With a
    :class:`PagedKVCache` (and ``slot``), the prompt's k/v scatter into
    that slot's pool blocks instead of a dense row — the attention math
    is untouched (prompt-internal attention never needs the pool)."""
    a = layer["attn"]
    ln1_out = _layer_norm(x, layer["ln1"], cfg.layer_norm_eps)
    h = ln1_out if cfg.pre_layer_norm else x
    q, k, v = _qkv(h, a, cfg, positions)
    if isinstance(cache, PagedKVCache):
        cache = paged_write_prompt(cache, layer_idx, k[0], v[0], slot)
    elif cache is not None:
        cache = write_prompt(cache, layer_idx, k, v, lengths)
    window = (cfg.local_windows[layer_idx] if cfg.local_windows else None)
    attn = _prefill_attention(q, k, v, cfg, causal=causal, key_mask=key_mask,
                              window=window, mesh=mesh)
    attn_out = _attn_out(attn, a, "...hd,hde->...e", x.dtype, cfg)
    return _post_attn(x, ln1_out, attn_out, layer, cfg, mesh), cache


def _block_decode(x, layer, cfg, cache, layer_idx, mesh=None):
    """Single-token block. x [B, E]; appends to cache."""
    a = layer["attn"]
    ln1_out = _layer_norm(x, layer["ln1"], cfg.layer_norm_eps)
    h = ln1_out if cfg.pre_layer_norm else x
    positions = cache.lengths  # new token position per row
    q, k, v = _qkv(h, a, cfg, positions)
    cache = append_token(cache, layer_idx, k, v)
    window = (cfg.local_windows[layer_idx] if cfg.local_windows else None)
    attn = _decode_attention(q, cache.k[layer_idx], cache.v[layer_idx],
                             cache.lengths + 1, cfg, window=window,
                             mesh=mesh)
    attn_out = _attn_out(attn, a, "bhd,hde->be", x.dtype, cfg)
    return _post_attn(x, ln1_out, attn_out, layer, cfg, mesh), cache


def _block_chunk(x, layer, cfg, cache, layer_idx, mesh=None):
    """K-token verify block (speculative decoding). x [B, K, E]; writes
    the chunk's k/v at per-row offsets without advancing lengths."""
    a = layer["attn"]
    ln1_out = _layer_norm(x, layer["ln1"], cfg.layer_norm_eps)
    h = ln1_out if cfg.pre_layer_norm else x
    K = x.shape[1]
    positions = cache.lengths[:, None] + jnp.arange(K)[None, :]  # [B, K]
    q, k, v = _qkv(h, a, cfg, positions)
    cache = write_chunk(cache, layer_idx, k, v)
    window = (cfg.local_windows[layer_idx] if cfg.local_windows else None)
    attn = _chunk_attention(q, cache.k[layer_idx], cache.v[layer_idx],
                            cache.lengths, cfg, window=window)
    attn_out = _attn_out(attn, a, "...hd,hde->...e", x.dtype, cfg)
    return _post_attn(x, ln1_out, attn_out, layer, cfg, mesh), cache


def decode_chunk(params, cfg: InferenceTransformerConfig, tokens,
                 cache: KVCache, mesh=None):
    """Speculative verify: score K candidate tokens ``[B, K]`` in ONE
    forward at positions ``lengths[b]..lengths[b]+K-1`` → (logits
    ``[B, K, V]``, cache). The chunk's k/v are written into the cache;
    lengths are NOT advanced — the caller commits the accepted prefix by
    advancing per-row (rejected positions remain masked garbage). This
    is the target-model half of speculative decoding; there is no
    reference analog (the reference's engine is strictly one-token
    decode, csrc/transformer/inference)."""
    _own_decoder_only(cfg, "decode_chunk (speculative verify)")
    if cfg.seq_shard_kv:
        raise NotImplementedError(
            "decode_chunk with seq-sharded KV is unsupported — run "
            "speculative decoding without seq_shard_kv")
    B, K = tokens.shape
    positions = cache.lengths[:, None] + jnp.arange(K)[None, :]
    x = _embed(params, cfg, tokens, positions)
    for i, layer in enumerate(params["layers"]):
        x, cache = _block_chunk(x, layer, cfg, cache, i, mesh)
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
    return _logits(params, cfg, x), cache


# ---------------------------------------------------------------- model

@scoped("embed")
def _embed(params, cfg, ids, positions, token_type_ids=None):
    x = params["wte"][ids].astype(cfg.dtype)
    if cfg.embed_scale != 1.0:   # Gemma: x * sqrt(E), head reads raw wte
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    if cfg.positional == "learned":
        x = x + params["wpe"][positions].astype(cfg.dtype)
    if "wtte" in params:  # BERT token-type embeddings
        tt = (token_type_ids if token_type_ids is not None
              else jnp.zeros_like(ids))
        x = x + params["wtte"][tt].astype(cfg.dtype)
    if "ln_emb" in params:  # BLOOM word_embeddings_layernorm / BERT emb LN
        x = _layer_norm(x, params["ln_emb"], cfg.layer_norm_eps)
    return x


@scoped("lm_head")
def _logits(params, cfg, x):
    head = (params["wte"].T if cfg.tied_lm_head else params["lm_head"])
    out = (x @ head.astype(x.dtype)).astype(jnp.float32)
    if "lm_head_bias" in params:  # GPT-J ships a biased lm_head
        out = out + params["lm_head_bias"].astype(jnp.float32)
    return out


def _causal_trunk(params, cfg, input_ids, lengths, cache, key_mask=None,
                  mesh=None, slot=None):
    """Shared causal forward trunk: embed → blocks → final LN. ``prefill``
    and ``causal_forward`` both run through here so full-sequence scoring
    can never diverge from generation."""
    B, T = input_ids.shape
    positions = jnp.arange(T)[None, :].repeat(B, 0)
    x = _embed(params, cfg, input_ids, positions)
    for i, layer in enumerate(params["layers"]):
        x, cache = _block_seq(x, layer, cfg, positions, lengths, cache, i,
                              causal=True, key_mask=key_mask, mesh=mesh,
                              slot=slot)
    return _layer_norm(x, params["ln_f"], cfg.layer_norm_eps), cache


def prefill(params, cfg: InferenceTransformerConfig, input_ids, lengths,
            cache: KVCache, mesh=None):
    """Run the right-padded prompt ``[B, T]`` through the model, populating
    the cache. Returns (next-token logits ``[B, V]``, cache)."""
    _own_decoder_only(cfg, "prefill into a dense KV cache (generate)")
    x, cache = _causal_trunk(params, cfg, input_ids, lengths, cache,
                             mesh=mesh)
    # logits at the last live token of each row
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return _logits(params, cfg, last), cache


def decode_step(params, cfg: InferenceTransformerConfig, tokens,
                cache: KVCache, mesh=None):
    """One generation step: ``tokens [B]`` int32 → (logits [B, V], cache).
    Appends k/v for the new token and advances lengths."""
    _own_decoder_only(cfg, "decode_step over a dense KV cache (generate)")
    x = _embed(params, cfg, tokens[:, None], cache.lengths[:, None])[:, 0]
    for i, layer in enumerate(params["layers"]):
        x, cache = _block_decode(x, layer, cfg, cache, i, mesh)
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
    return _logits(params, cfg, x), advance(cache)


def _block_decode_paged(x, layer, cfg, cache: PagedKVCache, layer_idx,
                        mesh=None):
    """Single-token block over the paged pool. x [S, E] (one token per
    SLOT); appends into each slot's current block."""
    a = layer["attn"]
    ln1_out = _layer_norm(x, layer["ln1"], cfg.layer_norm_eps)
    h = ln1_out if cfg.pre_layer_norm else x
    positions = cache.lengths
    q, k, v = _qkv(h, a, cfg, positions)
    cache = paged_append_token(cache, layer_idx, k, v)
    window = (cfg.local_windows[layer_idx] if cfg.local_windows else None)
    attn = _paged_decode_attention(q, cache, layer_idx, cfg,
                                   cache.lengths + 1, window=window,
                                   mesh=mesh)
    attn_out = _attn_out(attn, a, "bhd,hde->be", x.dtype, cfg)
    return _post_attn(x, ln1_out, attn_out, layer, cfg, mesh), cache


def paged_prefill(params, cfg: InferenceTransformerConfig, input_ids,
                  length, cache: PagedKVCache, slot, mesh=None):
    """Admit one prompt into pool slot ``slot``: run the right-padded
    ``[1, T]`` prompt through the trunk (prompt-internal attention needs
    no pool), scattering each layer's k/v into the slot's blocks, and pin
    ``lengths[slot]``. Returns (next-token logits ``[1, V]``, cache).

    ``slot`` is a traced scalar, so one trace per prompt BUCKET serves
    every slot; T must be a multiple of the pool block size."""
    family = model_family(cfg)
    if family is not None:
        return family.paged_prefill(params, cfg, input_ids, length, cache,
                                    slot, mesh=mesh)
    if cfg.seq_shard_kv:
        raise NotImplementedError(
            "paged serving with a seq-sharded KV pool is unsupported — "
            "the block pool is already the long-context memory lever")
    x, cache = _causal_trunk(params, cfg, input_ids, length, cache,
                             mesh=mesh, slot=slot)
    last = jnp.take_along_axis(x, (length - 1)[:, None, None], axis=1)[:, 0]
    cache = cache.replace(
        lengths=jax.lax.dynamic_update_index_in_dim(
            cache.lengths, length[0].astype(jnp.int32), slot, 0))
    return _logits(params, cfg, last), cache


def _block_chunk_paged(x, layer, cfg, cache: PagedKVCache, layer_idx,
                       slot, start, mesh=None):
    """Chunked-prefill block over the paged pool. x ``[1, C, E]`` at
    absolute positions ``start..start+C-1``; scatters the chunk's k/v
    into the slot's blocks, then attends over resident-prefix + chunk
    through the block table."""
    a = layer["attn"]
    ln1_out = _layer_norm(x, layer["ln1"], cfg.layer_norm_eps)
    h = ln1_out if cfg.pre_layer_norm else x
    C = x.shape[1]
    positions = start + jnp.arange(C)[None, :]               # [1, C]
    q, k, v = _qkv(h, a, cfg, positions)
    cache = paged_write_chunk(cache, layer_idx, k[0], v[0], slot, start)
    window = (cfg.local_windows[layer_idx] if cfg.local_windows else None)
    attn = _paged_chunk_attention(q, cache, layer_idx, cfg, slot, start,
                                  window=window, mesh=mesh)
    attn_out = _attn_out(attn, a, "...hd,hde->...e", x.dtype, cfg)
    return _post_attn(x, ln1_out, attn_out, layer, cfg, mesh), cache


def paged_prefill_chunk(params, cfg: InferenceTransformerConfig,
                        input_ids, start, length, cache: PagedKVCache,
                        slot, mesh=None):
    """One chunk of an incremental (Sarathi-style) prefill: run the
    C-token chunk ``input_ids [1, C]`` at absolute positions
    ``start..start+C-1`` through the trunk, scattering each layer's k/v
    into slot ``slot``'s blocks and attending over the already-resident
    prefix (earlier chunks, prefix-cache hits) through the block table.
    Returns (next-token logits ``[1, V]``, cache).

    ``start``/``slot`` are traced scalars and ``length [1]`` a traced
    array, so ONE trace per (C, pool geometry) serves every chunk of
    every prompt — the whole point vs the bucketed monolithic
    :func:`paged_prefill` (log2 shapes) when prompts are long or
    partially cached. ``lengths[slot]`` advances to
    ``min(start + C, length)`` so interleaved decode steps for OTHER
    slots see a consistent live bound (this slot stays inactive until
    the final chunk); the logits are only meaningful on the final chunk
    (the one containing position ``length - 1``) — earlier chunks
    return the chunk-tail row, which the caller discards. Chunk
    right-pad past ``length`` lands as masked garbage, overwritten by
    the first decode appends — the standard bucket-padding invariant."""
    family = model_family(cfg)
    if hasattr(family, "paged_prefill_chunk"):
        return family.paged_prefill_chunk(params, cfg, input_ids, start,
                                          length, cache, slot, mesh=mesh)
    _own_decoder_only(cfg, "paged_prefill_chunk (chunked prefill)")
    if cfg.seq_shard_kv:
        raise NotImplementedError(
            "paged serving with a seq-sharded KV pool is unsupported — "
            "the block pool is already the long-context memory lever")
    B, C = input_ids.shape
    positions = start + jnp.arange(C)[None, :]
    x = _embed(params, cfg, input_ids, positions)
    for i, layer in enumerate(params["layers"]):
        x, cache = _block_chunk_paged(x, layer, cfg, cache, i, slot,
                                      start, mesh)
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
    # the prompt's last token, when this chunk holds it; clamped to the
    # chunk tail otherwise (discarded by the host loop)
    li = jnp.clip(length[0] - 1 - start, 0, C - 1)
    last = jnp.take_along_axis(x, jnp.reshape(li, (1, 1, 1)),
                               axis=1)[:, 0]
    new_len = jnp.minimum(start + C, length[0]).astype(jnp.int32)
    cache = cache.replace(
        lengths=jax.lax.dynamic_update_index_in_dim(
            cache.lengths, new_len, slot, 0))
    return _logits(params, cfg, last), cache


def _block_verify_paged(x, layer, cfg, cache: PagedKVCache, layer_idx,
                        mesh=None):
    """K-token speculative-verify block over the paged pool. x
    ``[S, K, E]`` (one candidate chunk per SLOT); writes each slot's
    chunk k/v at per-slot offsets ``lengths[s]..lengths[s]+K-1``
    through the block tables without advancing lengths — the paged
    analog of :func:`_block_chunk`."""
    a = layer["attn"]
    ln1_out = _layer_norm(x, layer["ln1"], cfg.layer_norm_eps)
    h = ln1_out if cfg.pre_layer_norm else x
    K = x.shape[1]
    positions = cache.lengths[:, None] + jnp.arange(K)[None, :]  # [S, K]
    q, k, v = _qkv(h, a, cfg, positions)
    cache = paged_write_tokens(cache, layer_idx, k, v)
    window = (cfg.local_windows[layer_idx] if cfg.local_windows else None)
    attn = _paged_verify_attention(q, cache, layer_idx, cfg,
                                   window=window, mesh=mesh)
    attn_out = _attn_out(attn, a, "...hd,hde->...e", x.dtype, cfg)
    return _post_attn(x, ln1_out, attn_out, layer, cfg, mesh), cache


def paged_verify_step(params, cfg: InferenceTransformerConfig, tokens,
                      cache: PagedKVCache, mesh=None):
    """Speculative verify for ALL resident slots: score each slot's
    K-token candidate chunk ``tokens [S, K]`` in ONE forward at
    positions ``lengths[s]..lengths[s]+K-1`` → (logits ``[S, K, V]``,
    cache). The chunk's k/v are written through the block tables;
    lengths are NOT advanced — the caller commits the accepted prefix
    by advancing per-slot lengths host-side (rejected positions remain
    masked garbage beyond ``lengths``, overwritten by the next round —
    the same rollback-free invariant as :func:`decode_chunk` on the
    dense cache). ONE traced signature per ``(K, num_slots,
    block_size)``: per-slot acceptance state rides in ``lengths``, so
    varying acceptance lengths never retrace."""
    _own_decoder_only(cfg, "paged_verify_step (speculative verify)")
    if cfg.seq_shard_kv:
        raise NotImplementedError(
            "paged serving with a seq-sharded KV pool is unsupported — "
            "the block pool is already the long-context memory lever")
    S, K = tokens.shape
    positions = cache.lengths[:, None] + jnp.arange(K)[None, :]
    x = _embed(params, cfg, tokens, positions)
    for i, layer in enumerate(params["layers"]):
        x, cache = _block_verify_paged(x, layer, cfg, cache, i, mesh)
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
    return _logits(params, cfg, x), cache


def paged_decode_step(params, cfg: InferenceTransformerConfig, tokens,
                      cache: PagedKVCache, active, mesh=None):
    """One generation step for ALL resident slots: ``tokens [S]`` int32 →
    (logits ``[S, V]``, cache). Appends each slot's token at
    ``lengths[s]`` and advances only ``active`` slots — idle slots stay
    pinned at length 0, writing into the reserved null block, so one
    traced program serves every request mix."""
    family = model_family(cfg)
    if family is not None:
        return family.paged_decode_step(params, cfg, tokens, cache, active,
                                        mesh=mesh)
    x = _embed(params, cfg, tokens[:, None], cache.lengths[:, None])[:, 0]
    for i, layer in enumerate(params["layers"]):
        x, cache = _block_decode_paged(x, layer, cfg, cache, i, mesh)
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
    return _logits(params, cfg, x), paged_advance(cache, active)


def causal_forward(params, cfg: InferenceTransformerConfig, input_ids,
                   attention_mask=None, mesh=None):
    """Full-sequence logits ``[B, T, V]`` for causal models — the shape the
    reference ``InferenceEngine.forward`` returns (inference/engine.py:495),
    so scoring/perplexity loops indexing ``logits[:, i]`` port unchanged.
    ``attention_mask [B, T]`` masks pad keys (HF semantics) so padded rows
    are not scored against pad context. No cache; ``generate`` keeps the
    last-token fast path."""
    family = model_family(cfg)
    if family is not None:
        return family.causal_forward(params, cfg, input_ids,
                                     attention_mask, mesh=mesh)
    x, _ = _causal_trunk(params, cfg, input_ids, None, None,
                         key_mask=attention_mask, mesh=mesh)
    if cfg.head == "none":
        return x
    return _logits(params, cfg, x)


def encoder_forward(params, cfg: InferenceTransformerConfig, input_ids,
                    attention_mask=None, token_type_ids=None, mesh=None):
    """Bidirectional encoder forward (BERT/DistilBERT policies). Returns
    final hidden states ``[B, T, E]``."""
    _own_decoder_only(cfg, "encoder_forward")
    B, T = input_ids.shape
    positions = jnp.arange(T)[None, :].repeat(B, 0)
    x = _embed(params, cfg, input_ids, positions, token_type_ids)
    mask = (attention_mask if attention_mask is not None
            else jnp.ones((B, T), jnp.int32))
    lengths = jnp.sum(mask, -1).astype(jnp.int32)
    for i, layer in enumerate(params["layers"]):
        x, _ = _block_seq(x, layer, cfg, positions, lengths, None, i,
                          causal=False, key_mask=mask, mesh=mesh)
    if cfg.pre_layer_norm:
        x = _layer_norm(x, params["ln_f"], cfg.layer_norm_eps)
    return x
