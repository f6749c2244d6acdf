"""The LongCat-Flash family: how a configuration file's ``model`` block
becomes the program's serving model (``model_implementations/
longcat_flash.py``: one chip's share of an expert-parallel deployment)
and how its parameter tree is handed to the plain reference
(``benchmark/lib/reference_longcat.py``). Serving only: the family has
no training model."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import reference_longcat as reference  # noqa: F401

# keys of the model block that are the program's configuration fields,
# under the names the published config.json gives them
PUBLISHED = ("vocab_size", "hidden_size", "num_layers",
             "num_attention_heads", "ffn_hidden_size",
             "expert_ffn_hidden_size", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "mla_scale_q_lora", "mla_scale_kv_lora", "n_routed_experts",
             "zero_expert_num", "moe_topk", "routed_scaling_factor",
             "rms_norm_eps", "rope_theta", "max_position_embeddings")


def shapes(model: dict) -> dict:
    """Sizes the operation and byte functions (``lib/flops_longcat.py``)
    need."""
    return {"hidden": model["hidden_size"], "layers": model["num_layers"],
            "latent_width": model["kv_lora_rank"]
            + model["qk_rope_head_dim"],
            "expert_ffn": model["expert_ffn_hidden_size"],
            "top_k": model["moe_topk"],
            "itemsize": jnp.dtype(model["dtype"]).itemsize}


def serve_model(model: dict, seed: int):
    """``(LongcatFlashConfig, params)`` with seeded weights made on the
    device, in the type they are served in."""
    from deepspeed_tpu.model_implementations.longcat_flash import (
        LongcatFlashConfig, init_params)
    cfg = LongcatFlashConfig(
        dtype=jnp.dtype(model["dtype"]),
        experts_held=tuple(model["experts_held"]),
        **{k: model[k] for k in PUBLISHED})
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def reference_from_serve(cfg, params) -> dict:
    """The serving tree in the reference's layout. No array is copied:
    the reference reads the served (bfloat16) arrays and raises them to
    float32 a sub-block at a time."""
    lo, hi = cfg.experts_held
    layers = []
    for layer in params["layers"]:
        moe = layer["moe"]
        layers.append({
            "norm_in": layer["norm_in"], "norm_post": layer["norm_post"],
            "attn": [{"w_qa": a["wq_a"], "g_q": a["q_norm"],
                      "w_qb": a["wq_b"], "w_kva": a["wkv_a"],
                      "g_kv": a["kv_norm"], "w_kb": a["wk_b"],
                      "w_vb": a["wv_b"],
                      "w_o": a["wo"]} for a in layer["attn"]],
            "ffn": [{"w_gate_up": f["w_in"], "w_down": f["w_out"]}
                    for f in layer["ffn"]],
            "router": moe["router"], "router_bias": moe["router_bias"],
            "experts": {"w_gate_up": moe["experts"]["w_in"],
                        "w_down": moe["experts"]["w_out"]}})
    sizes = {"heads": cfg.num_attention_heads,
             "nope": cfg.qk_nope_head_dim, "rope": cfg.qk_rope_head_dim,
             "v_dim": cfg.v_head_dim,
             "q_scale": (cfg.hidden_size / cfg.q_lora_rank) ** 0.5
             if cfg.mla_scale_q_lora else 1.0,
             "kv_scale": (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5
             if cfg.mla_scale_kv_lora else 1.0,
             "n_routed": cfg.n_routed_experts, "top_k": cfg.moe_topk,
             "factor": float(cfg.routed_scaling_factor),
             "eps": float(cfg.rms_norm_eps), "theta": float(cfg.rope_theta),
             "held_lo": lo, "held_hi": hi}
    return {"wte": params["wte"], "lm_head": params["lm_head"],
            "norm_f": params["norm_f"], "sizes": sizes, "layers": layers}
