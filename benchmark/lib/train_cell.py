"""Runner for configurations of ``kind: train`` under traffic of
``kind: train_job``: the program's engine (``deepspeed_tpu.initialize``)
takes whole optimizer steps on seeded batches.

The clock: the window opens at a step boundary after warm-up and closes
at the first step boundary at or after ``seconds``; both boundaries are
read after the step's loss has reached the host, which is after
everything the step computes. Only whole steps are counted.
"""
from __future__ import annotations

import gc
import time

from benchmark.lib import harness


def build(config: dict, traffic: dict, seed: int, family, devices, marks):
    """Model, seeded parameters, the batches of the run (made on the
    device from the seed) and the reference's loss on the first batch,
    then the engine. The reference runs BEFORE the engine exists, while
    the float32 parameters are all the device holds."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    model = config["model"]
    tm = family.train_model(model)
    params = family.train_params(tm, seed)
    jax.block_until_ready(params)
    marks.mark("weights")
    engine_json = traffic["engine"]
    mesh = build_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    dp = mesh.shape["data"] * mesh.shape["fsdp"]
    micro = engine_json["train_micro_batch_size_per_gpu"]
    gas = engine_json.get("gradient_accumulation_steps", 1)
    rows, seq = micro * gas * dp, int(traffic["seq_len"])
    n_batches = int(traffic.get("distinct_batches", 64))
    make = jax.jit(lambda key: jax.random.randint(
        key, (n_batches, rows, seq), 0, model["vocab_size"], jnp.int32))
    batches = make(jax.random.PRNGKey(seed ^ 0x5EED))
    batches.block_until_ready()
    ref_rows = int(traffic.get("reference_rows", rows))
    t0 = time.perf_counter()
    ref_loss = family.reference.loss(
        family.reference_from_train(tm, params), batches[0][:ref_rows],
        model["vocab_size"])
    ref_seconds = time.perf_counter() - t0
    marks.mark("reference_loss")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tm, model_parameters=params, mesh=mesh, config=engine_json)
    del params
    marks.mark("engine")
    return {"engine": engine, "batches": batches, "n_batches": n_batches,
            "rows": rows, "seq": seq, "ref_loss": ref_loss,
            "ref_rows": ref_rows, "ref_seconds": ref_seconds,
            "mesh": dict(mesh.shape)}


def run(cell: dict, args, t_start: float, family, devices) -> dict:
    import numpy as np

    config, traffic = cell["config"], cell["traffic"]
    compiles = harness.CompileCounter()
    marks = harness.Marks(t_start)
    marks.mark("imports")
    built = build(config, traffic, args.seed, family, devices, marks)
    engine, batches = built["engine"], built["batches"]
    tracer = harness.Tracer(bool(args.trace), cell["cell"]["name"])
    try:
        def step(i):
            with harness.span("bench:train_batch"):
                out = engine.train_batch(
                    {"input_ids": batches[i % built["n_batches"]]})
                return float(out["loss"])     # host transfer: step done

        # the first step is the one the reference is held against; with
        # fewer reference rows than the batch, the engine's loss over the
        # same rows is not available, so the whole batch must be used
        warm = []
        for i in range(int(traffic.get("warmup_steps", 2))):
            warm.append(step(i))
            marks.mark(f"warmup_step_{i}")
        compiled_before = compiles.count
        gc.collect()
        trace_steps = int(traffic.get("trace_steps", 2))
        losses, step_s = [], []
        tracer.start()
        setup_s = time.time() - t_start
        t0 = time.perf_counter()
        k = len(warm)
        while True:
            ta = time.perf_counter()
            losses.append(step(k))
            t1 = time.perf_counter()
            step_s.append(t1 - ta)
            k += 1
            if tracer.active and len(step_s) >= trace_steps:
                tracer.stop()      # between steps: in no step's time
            if t1 - t0 >= args.seconds:
                break
        window_s = t1 - t0
        harness.TAIL.mark("window_closed")
        compiles_in_window = compiles.count - compiled_before
        from benchmark.lib.peaks import memory_peak_bytes
        mem = memory_peak_bytes(devices)
    finally:
        tracer.stop()
        engine.destroy()
        harness.TAIL.mark("engine_destroyed")
    marks.at.append(["window_opens", round(setup_s, 3)])
    harness.log({"setup_marks": marks.at})
    harness.log({"step_seconds": step_s, "losses": losses,
                 "warmup_losses": warm, "reference_loss": built["ref_loss"],
                 "reference_seconds": built["ref_seconds"],
                 "mesh": built["mesh"]})
    tol = float(traffic["loss_tolerance"])
    checks = {
        "first_loss_matches_reference":
            abs(warm[0] - built["ref_loss"]) <= tol,
        "losses_finite": bool(np.isfinite(warm + losses).all()),
        "loss_fell": losses[-1] < warm[0],
        "no_compile_in_window": compiles_in_window == 0,
    }
    return {
        "kind": "train", "setup_s": setup_s, "window_s": window_s,
        "steps": len(step_s), "step_seconds": step_s,
        "tokens_per_step": built["rows"] * built["seq"],
        "rows": built["rows"], "seq": built["seq"],
        "micro": traffic["engine"]["train_micro_batch_size_per_gpu"],
        "gas": traffic["engine"].get("gradient_accumulation_steps", 1),
        "losses": losses, "first_loss": warm[0],
        "reference_loss": built["ref_loss"],
        "loss_error": abs(warm[0] - built["ref_loss"]),
        "compile_s": harness.watched_compile_seconds(),
        "jax_compile_s": compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "memory_peak_bytes": mem, "checks": checks,
        # each number compared, beside its limit (the last loss has to
        # be under the first: its limit is exclusive)
        "compared": {
            "first_loss_error": [abs(warm[0] - built["ref_loss"]), tol],
            "last_loss_less_first": [losses[-1] - warm[0], 0.0],
            "compiles_in_window": [compiles_in_window, 0]},
        "attempted": len(step_s), "failed": 0,
        "trace_steps": trace_steps, "tracer": tracer,
    }
