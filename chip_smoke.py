"""First contact with the chip: the trainer takes a few steps and the
server answers a few requests on one TPU, through the entry points a
user calls, at the full width of GPT-2 1.3B (n_embd 2048, 16 heads of
128, vocab 50257, sequence 1024, bf16) and, on one chip, at its full
depth of 24 layers. Depth is the only cut the four-chip run makes (see
``reduced`` in its first line); weights, batches and prompts come from
``--seed``; nothing is downloaded.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

One JSON line per phase, then as the last line exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check prints ``"ok": false`` there and exits non-zero. There
is no CPU branch: without a TPU the script fails before any model work.
These are smoke facts (did it run, is it right) — no rate, no
utilisation; speed is the benchmark's business.

This process is the only one that touches jax, and it starts no others:
a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

# GPT-2 1.3B as the repo publishes it (models/gpt2.py PRESETS) and the
# serving defaults (inference/config.py: block_size 128, num_slots 8)
PRESET = "gpt2-1.3b"
PUBLISHED_LAYERS = 24


@dataclasses.dataclass(frozen=True)
class Size:
    """What a run is sized by. The defaults are the chip run; the CPU
    rehearsal (tests/test_chip_smoke.py) passes a tiny one."""
    n_embd: int = 2048
    n_head: int = 16
    vocab: int = 50257
    seq: int = 1024
    train_layers: int = 24
    serve_layers: int = 24
    sharded_layers: int = 4    # --chips 4: the 1-device leg has no offload
    block_size: int = 128
    num_slots: int = 8
    micro: int = 2
    train_steps: int = 5
    new_tokens: int = 12
    chunk_tokens: int = 256
    dtype: str = "bfloat16"
    # the chip run proves by the compiled text that the Pallas kernels
    # (not interpret mode, not the XLA reference) answered, and that the
    # optimizer state sat in pinned host memory
    on_chip: bool = True

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def tie_tol(self) -> float:
        """How near the top logit two greedy choices must sit to count
        as a tie, relative to its magnitude: 8 bf16 ulps; f32 engines
        keep the tests' 0.05 (top logits are O(1) there)."""
        return 0.05 if self.dtype == "float32" else 8 * 2.0 ** -8

    def train_config(self, n_layer: int):
        import jax.numpy as jnp
        from deepspeed_tpu.models.gpt2 import config_for
        return config_for(PRESET, n_embd=self.n_embd, n_head=self.n_head,
                          n_layer=n_layer, vocab_size=self.vocab,
                          n_positions=self.seq, dtype=jnp.dtype(self.dtype))

    def serve_config(self, n_layer: int):
        import jax.numpy as jnp
        from deepspeed_tpu.model_implementations.transformer import (
            InferenceTransformerConfig)
        return InferenceTransformerConfig(
            vocab_size=self.vocab, n_positions=self.seq,
            n_embd=self.n_embd, n_layer=n_layer, n_head=self.n_head,
            dtype=jnp.dtype(self.dtype))


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def peak_bytes() -> int:
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# ------------------------------------------------------------ executables

def watched(prefix: str) -> list:
    from deepspeed_tpu.telemetry.compile_watch import all_watched
    return [w for w in all_watched() if w.name.startswith(prefix)]


def audit_programs(fns: list, kernel_names: tuple, size: Size) -> float:
    """No retrace, every call served by its AOT executable (the compile
    watch has no plain-dispatch fallback to degrade to), and — on the
    chip — the named programs hold a compiled Pallas kernel. Returns
    compile seconds."""
    seconds = 0.0
    for w in fns:
        # monolithic prefill is traced once per prompt-length bucket by
        # design; every other program has one signature for good
        check(w.name == "serve_prefill" or not w.retraces,
              f"{w.name} retraced: {w.retraces}")
        for rec in w.executables:
            check(rec.calls > 0, f"{w.name}: executable never ran")
            seconds += rec.compile_seconds
            if size.on_chip and w.name in kernel_names:
                check("tpu_custom_call" in rec.compiled.as_text(),
                      f"{w.name} holds no compiled Pallas kernel")
    for name in kernel_names:
        check(any(w.name == name and w.executables for w in fns),
              f"program {name} never ran")
    return round(seconds, 1)


# ---------------------------------------------------------------- kernels

def kernels_phase(size: Size, seed: int) -> dict:
    """Each decode-family kernel once on seeded inputs at the serving
    widths (fp and int8 pool) against its own ``*_reference`` — a token
    match between two paths that share a wrong kernel proves nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import decode_attention as da
    from deepspeed_tpu.ops.quant_core import quantize_int8

    dt = jnp.dtype(size.dtype)
    S, H, D, BS = size.num_slots, size.n_head, size.head_dim, size.block_size
    MB = size.seq // BS
    NB = S * MB + 1
    K, C = 4, min(size.chunk_tokens, size.seq // 2)
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    # a two-layer pool whose layers hold different data; the kernels
    # attend the SECOND (a wrong block offset reads the first)
    LAYERS, LAYER = 2, 1
    kp = jax.random.normal(next(ks), (LAYERS, NB, BS, H, D), jnp.float32)
    vp = jax.random.normal(next(ks), (LAYERS, NB, BS, H, D), jnp.float32)
    # every slot owns MB distinct blocks, in a shuffled order; lengths
    # span idle, sub-block, mid-block and full
    perm = jax.random.permutation(next(ks), jnp.arange(1, NB))
    bt = perm.reshape(S, MB).astype(jnp.int32)
    lens = jnp.asarray(
        ([0, 1, BS - 1, BS, BS + 7, 3 * BS + 5, size.seq - K, size.seq]
         * S)[:S], jnp.int32)
    q1 = jax.random.normal(next(ks), (S, H, D), jnp.float32).astype(dt)
    qk = jax.random.normal(next(ks), (S, K, H, D), jnp.float32).astype(dt)
    qc = jax.random.normal(next(ks), (C, H, D), jnp.float32).astype(dt)
    start = jnp.int32(BS * (MB // 2))
    check(int(start) + C <= size.seq, "chunk window overruns the table")
    vlens = jnp.minimum(lens, size.seq - K)   # verify writes K past lens

    def rows(pool):                  # as PagedKVCache stores it
        return pool.reshape(LAYERS, NB, BS, H * D)

    def int8(pool):
        q, s = quantize_int8(pool, -1)            # [L,NB,BS,H,D], [..,1]
        return rows(q), jnp.transpose(s[..., 0], (0, 1, 3, 2))  # [L,NB,H,BS]

    # (k, v, scales...) — the kernels and their references take the
    # scales as the two trailing positional-or-keyword arguments; a
    # kernel takes the whole pool and a layer, its reference that layer
    (k8, ksc), (v8, vsc) = int8(kp), int8(vp)
    pools = {"fp": (rows(kp.astype(dt)), rows(vp.astype(dt))),
             "int8": (k8, v8, ksc, vsc)}

    def scales(sc):
        return dict(zip(("k_scale", "v_scale"), sc))

    def kernel_of(fn):       # the whole pool and the layer to attend
        return lambda q, k, v, table, bound, *sc: fn(
            q, k, v, table, bound, layer=LAYER, **scales(sc))

    def oracle_of(fn):       # that layer alone
        return lambda q, k, v, table, bound, *sc: fn(
            q, k[LAYER], v[LAYER], table, bound,
            **scales(s[LAYER] for s in sc))

    cases = {}
    for tag, (k, v, *sc) in pools.items():
        for kind, q, table, bound in (("decode", q1, bt, lens),
                                      ("verify", qk, bt, vlens),
                                      ("chunk", qc, bt[1], start)):
            cases[f"paged_{kind}-{tag}"] = (
                kernel_of(getattr(da, f"paged_{kind}_attention")),
                oracle_of(getattr(da, f"paged_{kind}_attention_reference")),
                (q, k, v, table, bound, *sc))
    kd = kp[LAYER][bt].reshape(S, size.seq, H, D).astype(dt)  # dense caches
    vd = vp[LAYER][bt].reshape(S, size.seq, H, D).astype(dt)
    cases["decode"] = (
        lambda *a: da.decode_attention(*a, block_k=BS),
        da.decode_attention_reference, (q1, kd, vd, lens))

    errs = {}
    for name, (fn, ref, args) in sorted(cases.items()):
        compiled = jax.jit(fn).lower(*args).compile()
        if size.on_chip:
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name}: no compiled Pallas kernel in the program")
        got = np.asarray(compiled(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref(*args), np.float32)
        # rows with nothing to attend (length 0) are defined as zeros by
        # the kernel and as a uniform average by the dense oracle
        live = np.asarray(lens > 0)
        if name.startswith(("paged_decode", "decode")):
            got, want = got[live], want[live]
        check(np.isfinite(got).all(), f"{name}: non-finite output")
        errs[name] = float(np.max(np.abs(got - want)))
        check(errs[name] < 3e-2, f"{name}: max |kernel - reference| = "
                                 f"{errs[name]:.4f}")
    return {"max_abs_err": {k: round(v, 5) for k, v in errs.items()}}


# ------------------------------------------------------------------ train

def train_phase(size: Size, seed: int) -> dict:
    """``deepspeed_tpu.initialize`` with the README recipe — bf16, AdamW,
    ZeRO-3, ``offload_optimizer: cpu`` (on the chip ``auto`` resolves to
    the streamed pinned-host path) — and a few ``train_batch`` steps on
    one repeated seeded batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMModel

    model = GPT2LMModel(size.train_config(size.train_layers))
    params = model.init(jax.random.PRNGKey(seed), batch_size=1,
                        seq_len=min(size.seq, 128))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": size.micro,
                "bf16": {"enabled": size.dtype == "bfloat16"},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {
                    "stage": 3, "offload_optimizer": {"device": "cpu"}}})
    del params
    try:
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(engine.state.params))
        batch = {"input_ids": jnp.asarray(
            np.random.default_rng(seed).integers(
                0, size.vocab, size=(engine.train_batch_size, size.seq)),
            jnp.int32)}
        losses = [float(engine.train_batch(batch)["loss"])
                  for _ in range(size.train_steps)]
        check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
        check(losses[-1] < losses[0], f"loss did not fall: {losses}")
        kinds = sorted({str(x.sharding.memory_kind) for x in jax.tree.leaves(
            (engine.state.master, engine.state.opt_state))
            if hasattr(x, "sharding")})
        if size.on_chip:
            check(kinds == ["pinned_host"],
                  f"optimizer state not streamed from host memory: {kinds}")
        # the streamed step is one watched program; the host path the
        # CPU rehearsal takes (C++ Adam between two plain jits) has none
        steps = watched("train_step")
        if size.on_chip:
            check(len(steps) == 1 and len(steps[0].executables) == 1,
                  "expected exactly one train_step executable")
        compile_s = audit_programs(
            steps, ("train_step",) if size.on_chip else (), size)
    finally:
        engine.destroy()
    return {"n_layer": size.train_layers, "params": n_params,
            "global_batch": int(batch["input_ids"].shape[0]),
            "losses": [round(x, 4) for x in losses],
            "optimizer_state_memory": kinds,
            "compile_seconds": compile_s,
            "checkpoint": "not run (the state is 18 bytes a parameter; "
                          "scripts/tpu_ckpt_roundtrip.py is the chip "
                          "check of save and resume)"}


# ------------------------------------------------------------------ serve

def assert_tie_tolerant(engine, want: list, got: list, pad_to: int,
                        rel_tol: float) -> float:
    """The tests' greedy-parity rule (test_speculative_decoding.py
    ``_assert_equal_up_to_ties``): two numerically different but
    equivalent decode paths may only part at an argmax tie. At the first
    mismatch the shared prefix is re-scored by the full-sequence forward
    and both chosen tokens must sit within ``rel_tol`` of the top logit's
    magnitude. Returns the gap (0.0 when the rows are identical)."""
    import numpy as np
    if want == got:
        return 0.0
    check(len(want) == len(got), f"lengths differ: {len(want)} vs "
                                 f"{len(got)}")
    i = next(j for j in range(len(want)) if want[j] != got[j])
    # right-pad to one fixed length: causal, so the pad cannot reach
    # position i-1, and every re-score reuses one compiled forward
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :i] = want[:i]
    lg = np.asarray(engine.forward(ids)[0, i - 1], np.float32)
    top = float(lg.max())
    tol = rel_tol * max(1.0, abs(top))
    gap = abs(float(lg[want[i]] - lg[got[i]]))
    check(gap < tol and top - min(lg[want[i]], lg[got[i]]) < tol,
          f"non-tie divergence at token {i}: want {want[i]} "
          f"({lg[want[i]]:.4f}) got {got[i]} ({lg[got[i]]:.4f}), top "
          f"{top:.4f}, tolerance {tol:.4f}")
    return gap


def serve_phase(size: Size, seed: int) -> dict:
    """``InferenceEngine`` + ``ContinuousBatchingServer`` answering eight
    seeded requests in two legs, so that all three paged kernels run:
    the default configuration (monolithic prefill + paged decode), then
    chunked prefill + speculation (paged chunk + paged verify). Served
    greedy tokens must equal one-shot ``generate()``."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                         DeepSpeedInferenceConfig,
                                         InferenceEngine)
    from deepspeed_tpu.model_implementations.transformer import init_params

    cfg = size.serve_config(size.serve_layers)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    BS = size.block_size
    # less than one block, several blocks, and one long enough to be
    # chunked (three chunks in the second leg)
    room = size.seq - size.new_tokens - 8
    lengths = [5, BS // 3, BS - 1, BS + 2, 2 * BS + 9, 3 * BS + BS // 2,
               BS // 2, min(2 * size.chunk_tokens + BS + 60, room)]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, size.vocab, size=n).tolist()
               for n in lengths]
    def leg(name, kernel_programs, **knobs):
        earlier = set(watched("serve_"))
        eng = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
            dtype=size.dtype, max_out_tokens=size.seq, block_size=BS,
            num_slots=size.num_slots, **knobs))
        srv = ContinuousBatchingServer(eng)
        try:
            ids = [srv.submit(p, max_new_tokens=size.new_tokens)
                   for p in prompts]
            out = srv.drain()
            stats = srv.stats
        finally:
            srv.close()
        check(sorted(out) == sorted(ids), f"{name}: unfinished requests")
        ref = eng.generate(prompts, max_new_tokens=size.new_tokens)
        gaps = [assert_tie_tolerant(eng, ref[j], out[i], size.seq,
                                    size.tie_tol)
                for j, i in enumerate(ids)]
        check(stats["retraces"] == max(stats["prefill_traces"] - 1, 0),
              f"{name}: {stats['retraces']} serving retraces, more than "
              f"the {stats['prefill_traces']} prefill buckets explain")
        compile_s = audit_programs(
            [w for w in watched("serve_") if w not in earlier],
            kernel_programs, size)
        return {"requests": len(ids),
                "tokens_out": sum(len(out[i]) - len(p)
                                  for i, p in zip(ids, prompts)),
                "exact_rows": sum(g == 0.0 for g in gaps),
                "max_tie_gap": round(max(gaps), 4),
                "decode_traces": stats["decode_traces"],
                "prefill_traces": stats["prefill_traces"],
                "chunk_traces": stats["chunk_traces"],
                "verify_traces": stats["speculation"]["verify_traces"],
                "compile_seconds": compile_s}, stats

    default, st = leg("default", ("serve_prefill", "serve_decode"))
    check(st["decode_traces"] == 1, "decode traced "
                                    f"{st['decode_traces']} times")
    spec, st = leg("chunked+speculative",
                   ("serve_prefill_chunk", "serve_spec_verify"),
                   prefill_chunk_tokens=size.chunk_tokens,
                   speculation_tokens=4)
    check(st["chunk_traces"] == 1 and
          st["speculation"]["verify_traces"] == 1,
          f"chunk/verify traces: {st['chunk_traces']}/"
          f"{st['speculation']['verify_traces']}")
    check(st["prefill_chunks"] >= len(prompts) + 2,
          "the long prompt was not chunked")
    return {"n_layer": size.serve_layers, "prompt_lengths": lengths,
            "default": default, "chunked_speculative": spec,
            "compile_seconds": round(default["compile_seconds"]
                                     + spec["compile_seconds"], 1)}


# --------------------------------------------------------------- host ops

def host_ops_phase(seed: int) -> dict:
    """The C++ host optimizer builds from ``csrc/`` as committed (this
    copy is not a git repository) and matches the numpy definition — the
    op behind ``offload_optimizer`` with ``implementation: host``. A
    missing compiler raises; it is never a silent numpy run."""
    import numpy as np

    from deepspeed_tpu.ops.cpu_adam import DeepSpeedCPUAdam

    rng = np.random.default_rng(seed)
    w0 = rng.standard_normal(1 << 16).astype(np.float32)
    g = rng.standard_normal(1 << 16).astype(np.float32)
    outs = []
    for native in (True, False):
        opt = DeepSpeedCPUAdam(lr=1e-3, weight_decay=0.01,
                               use_native=native)
        check(opt.native == native, "cpu_adam did not build natively")
        w = {"w": w0.copy()}
        state = opt.init_state(w)
        for _ in range(3):
            opt.step(w, {"w": g}, state)
        outs.append(w["w"])
    err = float(np.max(np.abs(outs[0] - outs[1])))
    check(err < 1e-5, f"native Adam differs from numpy by {err}")
    return {"cpu_adam_native_vs_numpy": err}


# ---------------------------------------------------------------- sharded

def sharded_phase(size: Size, seed: int) -> dict:
    """--chips 4: the two sharded paths users depend on, each against
    its one-device twin in this same process. (a) ZeRO-3 over
    ``fsdp=4``: three steps, same seeds and global batch as a mesh of
    ``jax.devices()[:1]``; losses agree to bf16 tolerance and all four
    devices hold a comparable share of parameters and optimizer state.
    (b) ``tp_size=4`` greedy ``generate()`` against ``tp_size=1``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.inference import (DeepSpeedInferenceConfig,
                                         InferenceEngine)
    from deepspeed_tpu.model_implementations.transformer import init_params
    from deepspeed_tpu.models.gpt2 import GPT2LMModel

    devices = jax.devices()
    n = len(devices)
    check(n >= 4, f"--chips 4 needs four devices, found {n}")
    n = 4
    cfg = size.train_config(size.sharded_layers)
    global_batch = n * max(1, size.micro // 2)
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(seed).integers(
            0, size.vocab, size=(global_batch, size.seq)), jnp.int32)}

    def in_use():
        return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in devices[:n]]

    def train(mesh):
        gc.collect()
        base = in_use()
        model = GPT2LMModel(cfg)
        params = model.init(jax.random.PRNGKey(seed), batch_size=1,
                            seq_len=min(size.seq, 128))
        dp = mesh.shape["data"] * mesh.shape["fsdp"]
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, mesh=mesh,
            config={"train_micro_batch_size_per_gpu": global_batch // dp,
                    "bf16": {"enabled": size.dtype == "bfloat16"},
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-4}},
                    "zero_optimization": {"stage": 3}})
        del params
        try:
            losses = [float(engine.train_batch(batch)["loss"])
                      for _ in range(3)]
            gc.collect()
            held = [b - a for a, b in zip(base, in_use())]
            audit_programs(watched("train_step")[-1:], ("train_step",),
                           size)
        finally:
            engine.destroy()
        return losses, held

    one, _ = train(build_mesh(MeshConfig(data=1), devices=devices[:1]))
    four, held = train(build_mesh(MeshConfig(data=1, fsdp=n),
                                  devices=devices[:n]))
    check(all(np.isfinite(one + four)), f"non-finite loss: {one} {four}")
    tol = 5e-2 if size.dtype == "bfloat16" else 1e-4
    check(np.allclose(one, four, rtol=tol, atol=tol),
          f"fsdp={n} losses {four} != one-device losses {one}")
    if size.on_chip:
        # state spread over all four devices: nobody holds more than
        # twice the mean share (a run that put everything on device 0
        # would show ~4x there and ~0 elsewhere)
        mean = sum(held) / n
        check(mean > 0 and max(held) < 2 * mean and min(held) > mean / 2,
              f"state is not spread over the devices: bytes held {held}")

    icfg = size.serve_config(size.sharded_layers)
    iparams = init_params(jax.random.PRNGKey(seed), icfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, size.vocab, size=k).tolist()
               for k in (7, size.block_size + 3)]
    outs = {}
    for tp in (1, n):
        eng = InferenceEngine((icfg, iparams), DeepSpeedInferenceConfig(
            dtype=size.dtype, max_out_tokens=size.seq,
            tensor_parallel={"tp_size": tp}))
        outs[tp] = eng.generate(prompts, max_new_tokens=8)
        if tp == 1:
            ref_engine = eng
    gaps = [assert_tie_tolerant(ref_engine, a, b, size.seq, size.tie_tol)
            for a, b in zip(outs[1], outs[n])]
    return {"n_layer": size.sharded_layers, "global_batch": global_batch,
            "losses_one_device": [round(x, 4) for x in one],
            f"losses_fsdp{n}": [round(x, 4) for x in four],
            "state_bytes_per_device": held,
            "tp_exact_rows": sum(g == 0.0 for g in gaps),
            "tp_max_tie_gap": round(max(gaps), 4)}


# ------------------------------------------------------------------- main

def run_phases(phases, device: dict) -> int:
    """Run ``(name, thunk)`` pairs in order; one JSON line each. The
    first failure is printed, traced to stderr, and ends the run."""
    import traceback
    ok = True
    for name, thunk in phases:
        t0 = time.time()
        try:
            facts = thunk()
        except Exception as e:  # noqa: BLE001 — reported, then fatal
            traceback.print_exc()
            emit({"phase": name, "ok": False,
                  "seconds": round(time.time() - t0, 1),
                  "error": f"{type(e).__name__}: {e}"[:2000]})
            ok = False
            break
        emit({"phase": name, "ok": True,
              "seconds": round(time.time() - t0, 1), **facts,
              "peak_bytes_in_use": peak_bytes()})
        gc.collect()
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the sharded paths (ZeRO-3 fsdp=4, "
                         "tp_size=4) and what they are compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the device, before any model work or heavy import
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    if dev.platform != "tpu" or device["count"] != args.chips:
        emit({"ok": False, "device": device,
              "error": f"needs {args.chips} TPU device(s); there is no "
                       "CPU branch"})
        return 1

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.logging import logger
    for handler in logger.handlers:      # stdout carries the JSON lines
        handler.setStream(sys.stderr)
    size = Size()
    depths = ({"sharded": size.sharded_layers} if args.chips == 4 else
              {"train": size.train_layers, "serve": size.serve_layers})
    cuts = {k: [PUBLISHED_LAYERS, v] for k, v in depths.items()
            if v != PUBLISHED_LAYERS}
    emit({"phase": "device", "ok": True, **device,
          "compile_cache": enable_compile_cache(),
          "reduced": {"n_layer": cuts} if cuts else {}})
    if args.chips == 4:
        return run_phases(
            [("sharded", lambda: sharded_phase(size, args.seed))], device)
    return run_phases(
        [("kernels", lambda: kernels_phase(size, args.seed)),
         ("host_ops", lambda: host_ops_phase(args.seed)),
         ("train", lambda: train_phase(size, args.seed)),
         ("serve", lambda: serve_phase(size, args.seed))], device)


if __name__ == "__main__":
    sys.exit(main())
