"""The Granite hybrid family: how a configuration file's ``model`` block
becomes the program's serving model
(``model_implementations/granite_hybrid.py``: Mamba-2 layers over a state
a slot beside attention layers over the K/V block pool in one cache, one
chip's share of an expert-parallel deployment and one stage of its
pipeline) and how its parameter tree is handed to the plain reference
(``benchmark/lib/reference_granite.py``). Serving only: the family has no
training model (no backward pass through the chunked form exists)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import reference_granite as reference  # noqa: F401

# keys of the model block that are the program's configuration fields,
# under the names the published config.json gives them
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "shared_intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads",
             "num_local_experts", "num_experts_per_tok", "mamba_n_heads",
             "mamba_d_head", "mamba_d_state", "mamba_n_groups",
             "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
             "embedding_multiplier", "attention_multiplier",
             "residual_multiplier", "logits_scaling", "rms_norm_eps",
             "max_position_embeddings")


def shapes(model: dict) -> dict:
    """Sizes the operation and byte functions (``lib/flops_granite.py``,
    ``lib/flops_longcat.py``) need. ``layers`` is the count of EXPERT
    layers (what the shared MoE readers divide by), as LongCat's."""
    kinds = model["layer_types"]
    heads = model["mamba_n_heads"]
    state_itemsize = jnp.dtype(model["state_dtype"]).itemsize
    itemsize = jnp.dtype(model["dtype"]).itemsize
    return {"hidden": model["hidden_size"],
            "layers": len(kinds),
            "expert_ffn": model["intermediate_size"],
            "top_k": model["num_experts_per_tok"],
            "state_layers": kinds.count("mamba"),
            "mamba_heads": heads, "mamba_d_head": model["mamba_d_head"],
            "mamba_d_state": model["mamba_d_state"],
            "mamba_chunk": model["mamba_chunk_size"],
            "state_bytes": (heads * model["mamba_d_head"]
                            * model["mamba_d_state"] * state_itemsize),
            "kv_heads": model["num_key_value_heads"],
            "head_dim": (model["hidden_size"]
                         // model["num_attention_heads"]),
            "itemsize": itemsize}


def serve_model(model: dict, seed: int):
    """``(GraniteHybridConfig, params)`` with seeded weights made on the
    device, in the type they are served in."""
    from deepspeed_tpu.model_implementations.granite_hybrid import (
        GraniteHybridConfig, init_params)
    cfg = GraniteHybridConfig(
        dtype=jnp.dtype(model["dtype"]),
        state_dtype=jnp.dtype(model["state_dtype"]),
        experts_held=tuple(model["experts_held"]),
        layer_types=tuple(model["layer_types"]),
        **{k: model[k] for k in PUBLISHED})
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def reference_from_serve(cfg, params) -> dict:
    """The serving tree in the reference's layout. No array is copied:
    the reference reads the served (bfloat16) arrays and raises them to
    float32 a matrix at a time (the program's three input projections
    are ``W_in``'s column blocks ``z``, ``xBC``, ``dt`` as they are)."""
    def swiglu(f):
        return {"w_gate_up": f["w_in"], "w_down": f["w_out"]}
    layers = []
    for kind, layer in zip(cfg.layer_types, params["layers"]):
        moe = layer["moe"]
        out = {"kind": kind, "g_in": layer["norm_in"],
               "g_post": layer["norm_post"], "router": moe["router"],
               "experts": swiglu(moe["experts"]),
               "shared": swiglu(moe["shared"])}
        if kind == "mamba":
            m = layer["mamba"]
            out.update(w_in=(m["w_z"], m["w_xbc"], m["w_dt"]),
                       conv_w=m["conv_w"], conv_b=m["conv_b"],
                       dt_bias=m["dt_bias"], A_log=m["A_log"], D=m["D"],
                       g_norm=m["norm"], w_out=m["w_out"])
        else:
            a = layer["attn"]
            out.update(w_q=a["wq"], w_k=a["wk"], w_v=a["wv"], w_o=a["wo"])
        layers.append(out)
    lo, hi = cfg.experts_held
    sizes = {"kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
             "top_k": cfg.num_experts_per_tok,
             "eps": float(cfg.rms_norm_eps),
             "n_experts": cfg.num_local_experts, "held_lo": lo,
             "held_hi": hi, "heads": cfg.mamba_n_heads,
             "d_head": cfg.mamba_d_head, "d_state": cfg.mamba_d_state,
             "embedding_multiplier": float(cfg.embedding_multiplier),
             "attention_multiplier": float(cfg.attention_multiplier),
             "residual_multiplier": float(cfg.residual_multiplier),
             "logits_scaling": float(cfg.logits_scaling)}
    return {"wte": params["wte"], "norm_f": params["norm_f"],
            "sizes": sizes, "layers": layers}
