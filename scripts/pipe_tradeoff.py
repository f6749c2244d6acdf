"""Measure the pipeline-path tradeoff (VERDICT r3 #6).

Three ways to run the same pipelined training step:

1. host-driven 1F1B executor (``parallel/pipe/executor.py``) — depth-
   bounded activation memory, NO extra FLOPs, but per-instruction host
   dispatch and single-controller only (refuses non-addressable meshes).
2. compiled scan+ppermute pipeline, ``remat=True`` — one XLA program
   (multi-host capable), O(1) activation memory per stage, but re-pays
   the forward FLOPs in backward (GPipe+remat double-pay, 4/3x).
3. compiled, ``remat=False`` — one XLA program, no FLOPs double-pay,
   but autodiff stashes one residual set per tick (M x stage
   activations), the GPipe-saved memory profile.

Run on the 8-device virtual CPU mesh (pipe=4 x data=2):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/pipe_tradeoff.py

Single-chip TPU cannot host a pipe>1 mesh, so wall numbers here are CPU
(dispatch overhead is real host time; FLOPs ratios are analytic and
platform-independent). Results + the decision table live in
docs/parallelism.md.
"""
import json
import os
import sys
import time

# a virtual-device experiment: eight CPU devices stand in for a slice
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deepspeed_tpu.comm.mesh import (MeshConfig, build_mesh,  # noqa: E402
                                     set_global_mesh)
from deepspeed_tpu.parallel.pipe import (LayerSpec,  # noqa: E402
                                         PipelineEngine, PipelineModule,
                                         pipeline_apply,
                                         stack_layer_params)

C, L, PIPE, DATA, M, B = 64, 8, 4, 2, 8, 32
STEPS = 5


def layer(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def loss_fn(y, labels):
    return jnp.mean((y - labels) ** 2)


def make_params():
    k = jax.random.PRNGKey(0)
    return [{
        "w": jax.random.normal(jax.random.fold_in(k, i), (C, C)) * 0.3,
        "b": jax.random.normal(jax.random.fold_in(k, 100 + i), (C,)) * 0.1,
    } for i in range(L)]


def time_fn(fn, *args):
    fn(*args)  # warm/compile
    times = []
    for _ in range(STEPS):
        t = time.time()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.time() - t)
    return sorted(times)[len(times) // 2]


def main():
    mesh = build_mesh(MeshConfig(data=DATA, pipe=PIPE))
    set_global_mesh(mesh)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, C)), jnp.float32)
    labels = jnp.asarray(rng.normal(size=(B, C)), jnp.float32)
    params = make_params()
    stacked = stack_layer_params(params)

    results = {}

    # -- compiled paths: full value_and_grad step under one jit
    for name, remat in (("compiled_remat", True), ("compiled_noremat",
                                                   False)):
        @jax.jit
        def step(sp, x, labels, _remat=remat):
            def lf(sp):
                y = pipeline_apply(layer, sp, x, num_microbatches=M,
                                   mesh=mesh, remat=_remat)
                return loss_fn(y, labels)
            return jax.value_and_grad(lf)(sp)

        t = time_fn(step, stacked, x, labels)
        loss, grads = step(stacked, x, labels)
        results[name] = {"ms_per_step": round(t * 1e3, 2),
                         "loss": round(float(loss), 6)}

    # -- host-driven 1F1B executor
    import optax
    specs = [LayerSpec(lambda: layer) for _ in range(L)]
    pm = PipelineModule(specs, num_stages=PIPE,
                        partition_method="uniform", loss_fn=loss_fn)
    eng = PipelineEngine(pm, make_params(), optax.sgd(0.0),
                         micro_batches=M, mesh=mesh)

    def exec_step(x, labels):
        return eng.train_batch(x, labels)["loss"]

    t = time_fn(lambda a, b: jnp.float32(exec_step(a, b)), x, labels)
    loss = exec_step(x, labels)
    results["executor_1f1b"] = {"ms_per_step": round(t * 1e3, 2),
                                "loss": round(float(loss), 6)}

    # parity: all three compute the same loss (executor's first step is
    # pre-update with lr=0, so its loss matches the compiled ones)
    losses = [v["loss"] for v in results.values()]
    assert max(losses) - min(losses) < 1e-4, losses

    results["config"] = {"layers": L, "pipe": PIPE, "data": DATA,
                         "micro": M, "batch": B, "hidden": C,
                         "platform": jax.default_backend()}
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
