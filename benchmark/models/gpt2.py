"""The GPT-2 family: how a configuration file's ``model`` block becomes
the program's training model and serving model (through the repo's
public constructors), and how either parameter tree is handed to the
plain reference (``benchmark/lib/reference_gpt2.py``). A later family is
a file like this one beside it, named by ``model.family``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import reference_gpt2 as reference  # noqa: F401

SIZES = ("n_embd", "n_layer", "n_head", "vocab_size", "n_positions")


def shapes(model: dict) -> dict:
    """Sizes the FLOPs and bytes functions need."""
    E, H = model["n_embd"], model["n_head"]
    return {"n_embd": E, "n_layer": model["n_layer"], "n_head": H,
            "kv_heads": H, "head_dim": E // H, "ffn": 4 * E,
            "vocab": model["vocab_size"], "n_positions": model["n_positions"],
            "itemsize": jnp.dtype(model["dtype"]).itemsize}


def train_model(model: dict):
    """``GPT2LMModel`` from the preset, every size stated in the file
    passed explicitly (so the file, not the preset, is what runs)."""
    from deepspeed_tpu.models.gpt2 import GPT2LMModel, config_for
    sizes = {k: model[k] for k in SIZES}
    cfg = config_for(model["preset"], dtype=jnp.dtype(model["dtype"]),
                     **sizes, **model.get("overrides", {}))
    return GPT2LMModel(cfg)


def train_params(tm, seed: int):
    return tm.init(jax.random.PRNGKey(seed), batch_size=1,
                   seq_len=min(tm.config.n_positions, 128))


def serve_model(model: dict, seed: int):
    """``(InferenceTransformerConfig, params)`` with seeded weights made
    on the device in one jitted call, in the type they are served in."""
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, init_params)
    cfg = InferenceTransformerConfig(
        dtype=jnp.dtype(model["dtype"]), **{k: model[k] for k in SIZES},
        **model.get("overrides", {}))
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def reference_from_train(tm, params) -> dict:
    """The flax tree of ``models/gpt2.py`` in the reference's layout.
    flax's LayerNorm default epsilon is 1e-6."""
    cfg = tm.config
    layers = []
    for i in range(cfg.n_layer):
        h = params[f"h_{i}"]
        layers.append({
            "ln1_g": h["ln_1"]["scale"], "ln1_b": h["ln_1"]["bias"],
            "w_qkv": h["attn"]["c_attn"]["kernel"],
            "b_qkv": h["attn"]["c_attn"]["bias"],
            "w_o": h["attn"]["c_proj"]["kernel"],
            "b_o": h["attn"]["c_proj"]["bias"],
            "ln2_g": h["ln_2"]["scale"], "ln2_b": h["ln_2"]["bias"],
            "w_fc": h["mlp"]["c_fc"]["kernel"],
            "b_fc": h["mlp"]["c_fc"]["bias"],
            "w_proj": h["mlp"]["c_proj"]["kernel"],
            "b_proj": h["mlp"]["c_proj"]["bias"]})
    return {"wte": params["wte"], "wpe": params["wpe"],
            "lnf_g": params["ln_f"]["scale"], "lnf_b": params["ln_f"]["bias"],
            "eps": 1e-6, "n_head": cfg.n_head, "layers": layers}


def reference_from_serve(cfg, params) -> dict:
    """The serving tree of ``model_implementations/transformer.py``
    (per-head ``wq wk wv [E, H, D]``, ``wo [H, D, E]``) in the
    reference's layout."""
    E = cfg.n_embd
    layers = []
    for layer in params["layers"]:
        a, m = layer["attn"], layer["mlp"]
        layers.append({
            "ln1_g": layer["ln1"]["scale"], "ln1_b": layer["ln1"]["bias"],
            "w_qkv": jnp.concatenate(
                [a[k].reshape(E, -1) for k in ("wq", "wk", "wv")], axis=1),
            "b_qkv": jnp.concatenate(
                [a[k].reshape(-1) for k in ("bq", "bk", "bv")]),
            "w_o": a["wo"].reshape(-1, E), "b_o": a["bo"],
            "ln2_g": layer["ln2"]["scale"], "ln2_b": layer["ln2"]["bias"],
            "w_fc": m["wi"], "b_fc": m["bi"],
            "w_proj": m["wo"], "b_proj": m["bo"]})
    return {"wte": params["wte"], "wpe": params["wpe"],
            "lnf_g": params["ln_f"]["scale"], "lnf_b": params["ln_f"]["bias"],
            "eps": cfg.layer_norm_eps, "n_head": cfg.n_head,
            "layers": layers}
