"""The Nemotron-H family: how a configuration file's ``model`` block
becomes the program's serving model
(``model_implementations/nemotron_h.py``: layers that are ONE mixer each,
Mamba-2 with several B/C groups over a state a slot, attention over the
K/V block pool, expert layers that keep nothing, in one cache; one chip's
share of an expert-parallel deployment and one stage of its pipeline) and
how its parameter tree is handed to the plain reference
(``benchmark/lib/reference_nemotron.py``). Serving only: the family has
no training model."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import reference_nemotron as reference  # noqa: F401

# keys of the model block that are the program's configuration fields,
# under the names the published config.json gives them
PUBLISHED = ("vocab_size", "hybrid_override_pattern", "hidden_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "mamba_num_heads",
             "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
             "chunk_size", "moe_intermediate_size",
             "moe_shared_expert_intermediate_size", "n_routed_experts",
             "num_experts_per_tok", "n_group", "topk_group",
             "routed_scaling_factor", "layer_norm_epsilon",
             "max_position_embeddings")


def shapes(model: dict) -> dict:
    """Sizes the operation and byte functions (``lib/flops_nemotron.py``,
    ``lib/flops_granite.py``) and the shared readers need. ``layers`` is
    the count of EXPERT layers (what the shared MoE readers divide by),
    as Granite's and LongCat's."""
    pattern = model["hybrid_override_pattern"]
    heads = model["mamba_num_heads"]
    return {"hidden": model["hidden_size"],
            "layers": pattern.count("E"),
            "expert_ffn": model["moe_intermediate_size"],
            "top_k": model["num_experts_per_tok"],
            "state_layers": pattern.count("M"),
            "mamba_heads": heads, "mamba_d_head": model["mamba_head_dim"],
            "mamba_d_state": model["ssm_state_size"],
            "mamba_chunk": model["chunk_size"],
            "state_bytes": (heads * model["mamba_head_dim"]
                            * model["ssm_state_size"]
                            * jnp.dtype(model["state_dtype"]).itemsize),
            "kv_heads": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "itemsize": jnp.dtype(model["dtype"]).itemsize}


def serve_model(model: dict, seed: int):
    """``(NemotronHConfig, params)`` with seeded weights made on the
    device, in the type they are served in."""
    from deepspeed_tpu.model_implementations.nemotron_h import (
        NemotronHConfig, init_params)
    cfg = NemotronHConfig(
        dtype=jnp.dtype(model["dtype"]),
        state_dtype=jnp.dtype(model["state_dtype"]),
        experts_held=tuple(model["experts_held"]),
        **{k: model[k] for k in PUBLISHED})
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def reference_from_serve(cfg, params) -> dict:
    """The serving tree in the reference's layout. No array is copied:
    the reference reads the served (bfloat16) arrays and raises them to
    float32 a matrix at a time (the program's three input projections
    are ``W_in``'s column blocks ``z``, ``xBC``, ``dt`` as they are)."""
    layers = []
    for kind, layer in zip(cfg.hybrid_override_pattern, params["layers"]):
        out = {"kind": kind, "g": layer["norm"]}
        if kind == "M":
            m = layer["mamba"]
            out.update(w_in=(m["w_z"], m["w_xbc"], m["w_dt"]),
                       conv_w=m["conv_w"], conv_b=m["conv_b"],
                       dt_bias=m["dt_bias"], A_log=m["A_log"], D=m["D"],
                       g_norm=m["norm"], w_out=m["w_out"])
        elif kind == "*":
            a = layer["attn"]
            out.update(w_q=a["wq"], w_k=a["wk"], w_v=a["wv"], w_o=a["wo"])
        else:
            moe = layer["moe"]
            out.update(
                router=moe["router"], bias=moe["router_bias"],
                experts={"w_up": moe["experts"]["w_in"],
                         "w_down": moe["experts"]["w_out"]},
                shared={"w_up": moe["shared"]["w_in"],
                        "w_down": moe["shared"]["w_out"]})
        layers.append(out)
    lo, hi = cfg.experts_held
    sizes = {"kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
             "top_k": cfg.num_experts_per_tok,
             "eps": float(cfg.layer_norm_epsilon),
             "n_experts": cfg.n_routed_experts, "held_lo": lo,
             "held_hi": hi, "heads": cfg.mamba_num_heads,
             "d_head": cfg.mamba_head_dim, "d_state": cfg.ssm_state_size,
             "groups": cfg.n_groups,
             "routed_scaling_factor": float(cfg.routed_scaling_factor)}
    return {"wte": params["wte"], "w_head": params["lm_head"],
            "norm_f": params["norm_f"], "sizes": sizes, "layers": layers}
