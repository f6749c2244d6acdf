"""The MiMo-V2 family: how a configuration file's ``model`` block becomes
the program's serving model (``model_implementations/mimo_v2.py``: window
layers with a learned sink over their own key/value head count beside
full layers over fewer, keys wider than values, in one K/V pool with
rings; one chip's share of an expert-parallel deployment and one stage of
its pipeline) and how its parameter tree is handed to the plain reference
(``benchmark/lib/reference_mimo.py``). Serving only: the family has no
training model (the windowed flash kernel, a sink and values narrower
than keys have no backward)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import reference_mimo as reference  # noqa: F401

# keys of the model block that are the program's configuration fields,
# under the names the published config.json gives them
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "v_head_dim",
             "swa_num_attention_heads", "swa_num_key_value_heads",
             "swa_head_dim", "swa_v_head_dim", "sliding_window",
             "rope_theta", "swa_rope_theta", "partial_rotary_factor",
             "attention_value_scale", "add_swa_attention_sink_bias",
             "add_full_attention_sink_bias", "moe_intermediate_size",
             "n_routed_experts", "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "layernorm_epsilon",
             "max_position_embeddings")
PER_LAYER = ("hybrid_layer_pattern", "moe_layer_freq")


def shapes(model: dict) -> dict:
    """Sizes the operation and byte functions (``lib/flops_mimo.py``,
    ``lib/flops_longcat.py``) need. ``layers`` is the count of EXPERT
    layers (what the shared MoE readers divide by), as LongCat's."""
    pattern = model["hybrid_layer_pattern"]
    return {"hidden": model["hidden_size"],
            "layers": sum(model["moe_layer_freq"]),
            "expert_ffn": model["moe_intermediate_size"],
            "top_k": model["num_experts_per_tok"],
            "full_layers": pattern.count(0),
            "window_layers": pattern.count(1),
            "window": model["sliding_window"],
            "heads": model["num_attention_heads"],
            "full_kv_heads": model["num_key_value_heads"],
            "window_kv_heads": model["swa_num_key_value_heads"],
            "head_dim": model["head_dim"],
            "v_head_dim": model["v_head_dim"],
            "itemsize": jnp.dtype(model["dtype"]).itemsize}


def serve_model(model: dict, seed: int):
    """``(MiMoV2Config, params)`` with seeded weights made on the device,
    in the type they are served in."""
    from deepspeed_tpu.model_implementations.mimo_v2 import (MiMoV2Config,
                                                             init_params)
    cfg = MiMoV2Config(
        dtype=jnp.dtype(model["dtype"]),
        experts_held=tuple(model["experts_held"]),
        **{k: tuple(model[k]) for k in PER_LAYER},
        **{k: model[k] for k in PUBLISHED})
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def reference_from_serve(cfg, params) -> dict:
    """The serving tree in the reference's layout. No array is copied:
    the reference reads the served (bfloat16) arrays and raises them to
    float32 a matrix at a time."""
    def swiglu(f):
        return {"w_gate_up": f["w_in"], "w_down": f["w_out"]}
    layers = []
    for li, layer in enumerate(params["layers"]):
        window = bool(cfg.hybrid_layer_pattern[li])
        out = {"kind": "window" if window else "full",
               "theta": float(cfg.swa_rope_theta if window
                              else cfg.rope_theta),
               "g_in": layer["norm_in"], "g_post": layer["norm_post"],
               "w_q": layer["wq"], "w_k": layer["wk"], "w_v": layer["wv"],
               "w_o": layer["wo"]}
        if "sink" in layer:
            out["sink"] = layer["sink"]
        if "ffn" in layer:
            out["ffn"] = swiglu(layer["ffn"])
        else:
            moe = layer["moe"]
            out.update(router=moe["router"], router_bias=moe["router_bias"],
                       experts=swiglu(moe["experts"]))
        layers.append(out)
    lo, hi = cfg.experts_held
    sizes = {"rotary_dim": int(cfg.head_dim * cfg.partial_rotary_factor),
             "window": cfg.sliding_window,
             "value_scale": float(cfg.attention_value_scale),
             "top_k": cfg.num_experts_per_tok,
             "factor": float(cfg.routed_scaling_factor or 1.0),
             "norm_topk": bool(cfg.norm_topk_prob),
             "eps": float(cfg.layernorm_epsilon),
             "held_lo": lo, "held_hi": hi}
    return {"wte": params["wte"], "lm_head": params["lm_head"],
            "norm_f": params["norm_f"], "sizes": sizes, "layers": layers}
