"""The DeepSeek-V3 family (``model_type: deepseek_v3``; GigaChat3.1 is
the configuration the benchmark has): how a configuration file's
``model`` block becomes the program's serving model
(``model_implementations/deepseek_v3.py``: one chip's share of an
expert-parallel deployment over a latent paged cache) and how its
parameter tree is handed to the plain reference
(``benchmark/lib/reference_gigachat.py``). Serving only: the family has
no training model."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import reference_gigachat as reference  # noqa: F401

# keys of the model block that are the program's configuration fields,
# under the names the published config.json gives them (its
# ``rope_scaling`` group is handed over as ``rope_*``)
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "n_routed_experts", "n_shared_experts",
             "num_experts_per_tok", "n_group", "topk_group",
             "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps",
             "rope_theta", "max_position_embeddings")
ROPE_SCALING = ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "mscale", "mscale_all_dim")


def shapes(model: dict) -> dict:
    """Sizes the operation and byte functions (``lib/flops_gigachat.py``,
    ``lib/flops_longcat.py``) need. ``layers`` is the count of EXPERT
    layers (what the shared MoE readers divide by), as LongCat's."""
    return {"hidden": model["hidden_size"],
            "layers": model["num_hidden_layers"]
            - model["first_k_dense_replace"],
            "attentions": model["num_hidden_layers"],
            "heads": model["num_attention_heads"],
            "latent_width": model["kv_lora_rank"]
            + model["qk_rope_head_dim"],
            "kv_rank": model["kv_lora_rank"],
            "qk_dim": model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
            "nope_dim": model["qk_nope_head_dim"],
            "v_dim": model["v_head_dim"],
            "expert_ffn": model["moe_intermediate_size"],
            "top_k": model["num_experts_per_tok"],
            "itemsize": jnp.dtype(model["dtype"]).itemsize}


def serve_model(model: dict, seed: int):
    """``(DeepseekV3Config, params)`` with seeded weights made on the
    device, in the type they are served in."""
    from deepspeed_tpu.model_implementations.deepseek_v3 import (
        DeepseekV3Config, init_params)
    scaling = model["rope_scaling"]
    if scaling["rope_type"] != "yarn":
        raise NotImplementedError(f"rope_type {scaling['rope_type']!r}")
    cfg = DeepseekV3Config(
        dtype=jnp.dtype(model["dtype"]),
        experts_held=tuple(model["experts_held"]),
        **{"rope_" + k: scaling[k] for k in ROPE_SCALING},
        **{k: model[k] for k in PUBLISHED})
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def reference_from_serve(cfg, params) -> dict:
    """The serving tree in the reference's layout. No array is copied:
    the reference reads the served (bfloat16) arrays and raises them to
    float32 a matrix at a time."""
    def swiglu(f):
        return {"w_gate_up": f["w_in"], "w_down": f["w_out"]}
    layers = []
    for layer in params["layers"]:
        a = layer["attn"]
        out = {"g_in": layer["norm_in"], "g_post": layer["norm_post"],
               "w_qa": a["wq_a"], "g_q": a["q_norm"], "w_qb": a["wq_b"],
               "w_kva": a["wkv_a"], "g_kv": a["kv_norm"],
               "w_kb": a["wk_b"], "w_vb": a["wv_b"], "w_o": a["wo"]}
        if "ffn" in layer:
            out["ffn"] = swiglu(layer["ffn"])
        else:
            moe = layer["moe"]
            out.update(router=moe["router"], router_bias=moe["router_bias"],
                       experts=swiglu(moe["experts"]),
                       shared=swiglu(moe["shared"]))
        layers.append(out)
    lo, hi = cfg.experts_held
    yarn = {"factor": cfg.rope_factor,
            "original_max_position_embeddings":
                cfg.rope_original_max_position_embeddings,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim}
    sizes = {"nope": cfg.qk_nope_head_dim, "rope": cfg.qk_rope_head_dim,
             "v_dim": cfg.v_head_dim, "eps": float(cfg.rms_norm_eps),
             "theta": float(cfg.rope_theta),
             "yarn": tuple(sorted(yarn.items())),
             "top_k": cfg.num_experts_per_tok, "n_group": cfg.n_group,
             "topk_group": cfg.topk_group,
             "factor": float(cfg.routed_scaling_factor),
             "n_routed": cfg.n_routed_experts, "held_lo": lo, "held_hi": hi}
    return {"wte": params["wte"], "lm_head": params["lm_head"],
            "norm_f": params["norm_f"], "sizes": sizes, "layers": layers}
