"""The Brumby family: how a configuration file's ``model`` block becomes
the program's serving model (``model_implementations/brumby.py``: power
retention over a recurrent state pool, one stage of a pipeline) and how
its parameter tree is handed to the plain reference
(``benchmark/lib/reference_brumby.py``). Serving only: the family has no
training model (no backward pass through the chunked form exists)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import reference_brumby as reference  # noqa: F401

# keys of the model block that are the program's configuration fields,
# under the names the published config.json gives them, then the ones it
# does not state (the configuration file's ``assumed``)
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rms_norm_eps",
             "rope_theta", "max_position_embeddings")
ASSUMED = ("degree", "retention_eps", "chunk_size")


def shapes(model: dict) -> dict:
    """Sizes the operation and byte functions (``lib/flops_brumby.py``)
    need."""
    return {"hidden": model["hidden_size"],
            "layers": model["num_hidden_layers"],
            "heads": model["num_attention_heads"],
            "kv_heads": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "state_itemsize": jnp.dtype(model["state_dtype"]).itemsize,
            "itemsize": jnp.dtype(model["dtype"]).itemsize}


def serve_model(model: dict, seed: int):
    """``(BrumbyConfig, params)`` with seeded weights made on the device,
    in the type they are served in."""
    from deepspeed_tpu.model_implementations.brumby import (BrumbyConfig,
                                                            init_params)
    cfg = BrumbyConfig(
        dtype=jnp.dtype(model["dtype"]),
        state_dtype=jnp.dtype(model["state_dtype"]),
        **{k: model[k] for k in PUBLISHED + ASSUMED})
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def reference_from_serve(cfg, params) -> dict:
    """The serving tree in the reference's layout. No array is copied:
    the reference reads the served (bfloat16) arrays and raises them to
    float32 a matrix at a time."""
    layers = [{"g_in": layer["norm_in"], "g_post": layer["norm_post"],
               "w_q": layer["wq"], "w_k": layer["wk"], "w_v": layer["wv"],
               "w_g": layer["wg"], "b_g": layer["bg"],
               "g_qn": layer["q_norm"], "g_kn": layer["k_norm"],
               "w_o": layer["wo"], "w_gate_up": layer["w_in"],
               "w_down": layer["w_out"]} for layer in params["layers"]]
    sizes = {"heads": cfg.num_attention_heads,
             "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
             "eps": float(cfg.rms_norm_eps), "theta": float(cfg.rope_theta),
             "ret_eps": float(cfg.retention_eps)}
    return {"wte": params["wte"], "lm_head": params["lm_head"],
            "norm_f": params["norm_f"], "sizes": sizes, "layers": layers}
