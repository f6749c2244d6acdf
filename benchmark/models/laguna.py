"""The Laguna family: how a configuration file's ``model`` block becomes
the program's serving model (``model_implementations/laguna.py``: window
and full attention layers over one K/V pool with rings, one chip's share
of an expert-parallel deployment) and how its parameter tree is handed
to the plain reference (``benchmark/lib/reference_laguna.py``). Serving
only: the family has no training model (the windowed flash kernel has no
backward)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import reference_laguna as reference  # noqa: F401

# keys of the model block that are the program's configuration fields,
# under the names the published config.json gives them (its
# ``rope_parameters`` group is handed over by layer type)
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_key_value_heads", "head_dim",
             "sliding_window", "num_experts", "num_experts_per_tok",
             "moe_intermediate_size", "shared_expert_intermediate_size",
             "moe_routed_scaling_factor", "rms_norm_eps",
             "max_position_embeddings")
PER_LAYER = ("layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer")
ROPE_KEYS = ("rope_theta", "partial_rotary_factor", "rope_type", "factor",
             "original_max_position_embeddings", "beta_fast", "beta_slow",
             "attention_factor")


def shapes(model: dict) -> dict:
    """Sizes the operation and byte functions (``lib/flops_laguna.py``,
    ``lib/flops_longcat.py``) need. ``layers`` is the count of EXPERT
    layers (what the shared MoE readers divide by), as LongCat's."""
    kinds = model["layer_types"]
    return {"hidden": model["hidden_size"],
            "layers": model["mlp_layer_types"].count("sparse"),
            "expert_ffn": model["moe_intermediate_size"],
            "top_k": model["num_experts_per_tok"],
            "full_layers": kinds.count("full_attention"),
            "window_layers": kinds.count("sliding_attention"),
            "window": model["sliding_window"],
            "kv_heads": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "itemsize": jnp.dtype(model["dtype"]).itemsize}


def _rope_spec(group: dict):
    from deepspeed_tpu.model_implementations.laguna import RopeSpec
    return RopeSpec(**{k: group[k] for k in ROPE_KEYS if k in group})


def serve_model(model: dict, seed: int):
    """``(LagunaConfig, params)`` with seeded weights made on the device,
    in the type they are served in."""
    from deepspeed_tpu.model_implementations.laguna import (LagunaConfig,
                                                            init_params)
    rope = model["rope_parameters"]
    cfg = LagunaConfig(
        dtype=jnp.dtype(model["dtype"]),
        experts_held=tuple(model["experts_held"]),
        rope_full=_rope_spec(rope["full_attention"]),
        rope_sliding=_rope_spec(rope["sliding_attention"]),
        **{k: tuple(model[k]) for k in PER_LAYER},
        **{k: model[k] for k in PUBLISHED})
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def reference_from_serve(cfg, params) -> dict:
    """The serving tree in the reference's layout. No array is copied:
    the reference reads the served (bfloat16) arrays and raises them to
    float32 a matrix at a time."""
    import dataclasses

    def swiglu(f):
        return {"w_gate_up": f["w_in"], "w_down": f["w_out"]}
    layers = []
    for li, layer in enumerate(params["layers"]):
        spec = dataclasses.asdict(cfg.rope(li))
        out = {"kind": ("sliding" if cfg.window_layers[li] else "full"),
               "rope": tuple(sorted((k, v) for k, v in spec.items()
                                    if v is not None)),
               "g_in": layer["norm_in"], "g_post": layer["norm_post"],
               "w_q": layer["wq"], "w_k": layer["wk"], "w_v": layer["wv"],
               "w_g": layer["wg"], "w_o": layer["wo"]}
        if "ffn" in layer:
            out["ffn"] = swiglu(layer["ffn"])
        else:
            moe = layer["moe"]
            out.update(router=moe["router"], router_bias=moe["router_bias"],
                       experts=swiglu(moe["experts"]),
                       shared=swiglu(moe["shared"]))
        layers.append(out)
    lo, hi = cfg.experts_held
    sizes = {"kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
             "window": cfg.sliding_window, "top_k": cfg.num_experts_per_tok,
             "factor": float(cfg.moe_routed_scaling_factor),
             "eps": float(cfg.rms_norm_eps), "n_experts": cfg.num_experts,
             "held_lo": lo, "held_hi": hi}
    return {"wte": params["wte"], "lm_head": params["lm_head"],
            "norm_f": params["norm_f"], "sizes": sizes, "layers": layers}
