"""Inference engine.

Analog of ``deepspeed/inference/engine.py`` (``InferenceEngine``, ``:31``):
owns the (TP-sharded) weights, the jitted prefill/decode programs, the KV
cache, and a HF-style ``generate``. Differences by design:

* CUDA-graph capture/replay (``engine.py:454,473``) → jit compile cache:
  the decode step is traced once per (batch, cache) shape and replayed.
* TP process group (``:177``) → a ``tensor`` axis on a `jax.sharding.Mesh`;
  weights are placed with Megatron specs (model_implementations.tp_param_specs)
  and GSPMD inserts the per-layer allreduce.
* Kernel injection (``:325`` → replace_module) → checkpoint *conversion*:
  policies (deepspeed_tpu.module_inject) map HF weights into the fused
  functional transformer; no live module surgery.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import dataclasses

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.kv_cache import (KVCache, auto_max_tokens,
                                              init_cache)
# shared speculative primitives (inference/speculation.py): the server's
# per-slot speculative path uses the SAME acceptance/commit/proposal
# rules, so the one-shot and paged paths cannot drift. The leading-
# underscore aliases keep this module's historical names importable.
from deepspeed_tpu.inference.speculation import (
    commit_speculative_block as _commit_speculative_block,
    greedy_accept as _greedy_accept, lookup_proposals)
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, causal_forward, decode_chunk, decode_step,
    encoder_forward,
    init_params, model_family, prefill, tp_param_specs)
from deepspeed_tpu.telemetry import (MetricRegistry, get_registry,
                                     watched_jit)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _bucket(n: int, base: int = 128) -> int:
    """Geometric shape bucket: the smallest ``base * 2**k >= n``.

    Raw ``_round_up(n, 128)`` gives every distinct 128-span of prompt/
    budget lengths its own padded shape — and every distinct shape is a
    fresh trace + compile of the prefill program and the whole decode
    loop (the dominant serving cost after the first call). The ladder
    caps the trace count at ``log2(longest/128) + 1`` shapes total
    (128, 256, 512, ...). The price: up to 2x padding FLOPs on the
    prefill (the decode hot path reads live lengths, so dead cache tail
    costs no decode attention work), and the KV cache may allocate up to
    2x the raw need in HBM — bounded by ``max_out_tokens``, which is
    documented as the cache budget the caller has already signed up
    for (``_fit_to_budget`` never exceeds it)."""
    if n <= base:
        return base
    b = base
    while b < n:
        b *= 2
    return b


def _fit_to_budget(need: int, budget: int) -> int:
    """Bucketed cache size for ``need`` tokens under ``budget``: the
    geometric bucket, except a bucket that overshoots a budget the raw
    need fits is clamped TO the budget (one extra 'ceiling' shape) so
    bucketing never rejects a request the dense 128-rounding accepted.
    Returns 0 when even the raw need exceeds the budget (caller raises
    its budget error)."""
    if _round_up(need, 128) > budget:
        return 0
    return min(_bucket(need), budget)


def check_draft_compat(target, draft) -> None:
    """Validate a draft engine against its speculation target: LM heads
    on both sides and interchangeable token ids. Shared by the one-shot
    ``generate_speculative(draft=...)`` path and the paged server's
    ``speculation_draft`` wiring so both reject the same mismatches
    with the same message."""
    if target.model_config.head == "none" or \
            draft.model_config.head == "none":
        raise ValueError("speculative decoding needs LM heads on "
                         "both engines")
    if target.model_config.vocab_size != draft.model_config.vocab_size:
        raise ValueError(
            f"target/draft vocab sizes differ "
            f"({target.model_config.vocab_size} vs "
            f"{draft.model_config.vocab_size}) — token ids must be "
            "interchangeable")


class InferenceEngine:
    """Generation engine over the fused functional transformer.

    ``model`` is either ``(InferenceTransformerConfig, params)`` from a
    policy/converter, or an ``InferenceTransformerConfig`` (random init when
    ``set_empty_params``-style testing).
    """

    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None,
                 mesh: Optional[Mesh] = None):
        self.config = config or DeepSpeedInferenceConfig()
        if self.config.injection_policy is not None:
            # config-only check: fail BEFORE any multi-GB conversion/load
            raise NotImplementedError(
                "custom injection_policy dicts are torch-module surgery "
                "(reference replace_module.py) — register a conversion "
                "policy instead: subclass HFPolicy and decorate with "
                "deepspeed_tpu.module_inject.policies.register_policy")
        # dtype="int8" means WEIGHT STORAGE (reference GroupQuantizer):
        # activations run bf16, weights quantize to int8+scales at
        # placement time — resolved before conversion so the policy table
        # never casts weights to an integer dtype
        int8 = self.config.jnp_dtype == jnp.int8  # "int8"/"torch.int8"
        self._weight_quant = int8 or self.config.quant.enabled
        self._act_dtype = (jnp.bfloat16 if int8
                           else self.config.jnp_dtype)
        if isinstance(model, tuple):
            self.model_config, params = model
        elif isinstance(model, InferenceTransformerConfig):
            self.model_config = model
            params = init_params(jax.random.PRNGKey(0), model)
        elif model_family(model) is not None:
            # a configuration that names the module that runs it
            self.model_config = model
            params = model_family(model).init_params(jax.random.PRNGKey(0),
                                                     model)
        else:
            # torch nn.Module / HF model → policy conversion
            try:
                from deepspeed_tpu.module_inject import convert_hf_model
            except ImportError as e:
                raise NotImplementedError(
                    "HF-model conversion requires deepspeed_tpu.module_inject"
                    " (policy table); pass (InferenceTransformerConfig, "
                    "params) instead") from e
            self.model_config, params = convert_hf_model(
                model, dtype=self._act_dtype)
        if model_family(self.model_config) is not None:
            self._refuse_for_family()
        # engine dtype wins over the model config's (one source of truth):
        # activations are cast to model_config.dtype inside the forward
        self.model_config = dataclasses.replace(self.model_config,
                                                dtype=self._act_dtype)
        if not self.config.triangular_masking and \
                self.model_config.pre_layer_norm and \
                self.model_config.head != "none":
            raise NotImplementedError(
                "triangular_masking=False on a causal LM (bidirectional "
                "decoding) is not supported; encoder models are already "
                "bidirectional and ignore the flag")
        if self.config.quant.activation.enabled:
            # w8a8: dynamic activation quant at the MLP GEMM seams
            # (ops/int8_gemm.py) — only meaningful over int8-stored
            # weights, whether quantized HERE (config) or already stored
            # quantized (serving-checkpoint reload)
            def _tree_has_int8(tree):
                for path, _ in jax.tree_util.tree_flatten_with_path(
                        tree)[0]:
                    if any(getattr(p, "key", None) == "q" for p in path):
                        return True
                return False
            if not self._weight_quant and not _tree_has_int8(params):
                raise ValueError(
                    "quant.activation.enabled (w8a8 GEMMs) requires int8 "
                    "weight storage — set dtype='int8'/quant.enabled or "
                    "load an int8 serving checkpoint")
            self.model_config = dataclasses.replace(self.model_config,
                                                    int8_compute=True)
        self.mesh = mesh or self._build_mesh()
        if self.config.seq_parallel_size > 1:
            if self.mesh is None or "seq" not in self.mesh.axis_names:
                raise ValueError("seq_parallel_size>1 needs a mesh with "
                                 "a 'seq' axis")
            # the decode attention must take the GSPMD-partitionable
            # path — flag it on the model config
            self.model_config = dataclasses.replace(self.model_config,
                                                    seq_shard_kv=True)
        if self.mesh is not None:
            tp = self.config.tp_size
            if self.model_config.kv_heads % tp or \
                    self.model_config.n_head % tp:
                raise ValueError(
                    f"tp_size={tp} must divide n_head="
                    f"{self.model_config.n_head} and kv_heads="
                    f"{self.model_config.kv_heads}")
        self.params = self._place_params(params)
        # process-wide registry (docs/observability.md); tests swap in a
        # private MetricRegistry via this attribute. telemetry.enabled=
        # false records into a private registry instead — same cost,
        # nothing reaches the process scrape surface. (Resolved BEFORE
        # the jit wrappers below: the compile watch records retraces and
        # compile times into the same registry.)
        tcfg = getattr(self.config, "telemetry", None)
        self.telemetry = (get_registry() if tcfg is None or tcfg.enabled
                          else MetricRegistry())
        # request-scoped tracing (telemetry/tracing.py): a one-shot
        # generate() gets a two-level trace — root + dispatch/fetch
        # children — under the same sampling config the server uses
        self.tracer = None
        if tcfg is not None and tcfg.enabled and \
                tcfg.trace_sample_rate > 0:
            from deepspeed_tpu.telemetry import Tracer
            self.tracer = Tracer(
                sample_rate=tcfg.trace_sample_rate,
                ring_capacity=tcfg.trace_ring_capacity,
                seed=tcfg.trace_seed,
                slow_threshold_s=tcfg.trace_slow_threshold_s,
                registry=self.telemetry)
        # flight recorder (telemetry/compile_watch.py): every entry
        # point is watched, so an unexpected prompt shape shows up as a
        # `retrace` event naming the argument that changed, with the
        # compile wall time and the executable's flops/HBM footprint
        self._prefill_jit = watched_jit(
            functools.partial(prefill, cfg=self.model_config,
                              mesh=self.mesh),
            name="infer_prefill", registry=self.telemetry,
            donate_argnames=("cache",))
        self._decode_jit = watched_jit(
            functools.partial(decode_step, cfg=self.model_config,
                              mesh=self.mesh),
            name="infer_decode", registry=self.telemetry,
            donate_argnames=("cache",))
        self._encoder_jit = watched_jit(
            functools.partial(encoder_forward, cfg=self.model_config,
                              mesh=self.mesh),
            name="infer_encoder_forward", registry=self.telemetry)
        self._causal_fwd_jit = watched_jit(
            functools.partial(causal_forward, cfg=self.model_config,
                              mesh=self.mesh),
            name="infer_causal_forward", registry=self.telemetry)
        self._gen_loops: Dict[Any, Any] = {}

    def _refuse_for_family(self) -> None:
        """A model of another family (``model_family``: latent attention
        over a latent pool, retention over a state pool, window layers'
        rings or state-space layers' states beside the K/V block pool)
        runs on one device with full-precision weights: its parameter
        tree has no Megatron specs, it issues no exchange, and nothing
        quantizes its weights. Each switch that would need one of those
        (``dtype='int8'`` / ``quant.enabled``,
        ``quant.activation.enabled``, ``tp_size``, ``moe.ep_size``,
        ``seq_parallel_size``) is refused here by name."""
        c = self.config
        on = [name for name, is_on in (
            ("dtype='int8' / quant.enabled", self._weight_quant),
            ("quant.activation.enabled", c.quant.activation.enabled),
            ("tp_size", c.tp_size > 1),
            ("moe.ep_size", c.moe.ep_size > 1),
            ("seq_parallel_size", c.seq_parallel_size > 1)) if is_on]
        if on:
            raise NotImplementedError(
                f"a {type(self.model_config).__name__} model cannot be "
                f"served with {', '.join(on)}: it runs on one device as "
                "its share of a deployment (an expert-parallel layer's "
                "held experts, a pipeline's stage), with the weights in "
                "the serving dtype")

    def _loop_cache_get(self, key):
        """Decode-loop cache lookup with hit/miss telemetry: a rising
        miss count under steady traffic means request shapes are
        defeating the geometric buckets (the retrace regression)."""
        hit = self._gen_loops.get(key)
        if hit is not None:
            self.telemetry.counter(
                "inference_trace_cache_hits_total",
                help="decode-loop cache lookups (see "
                     "docs/observability.md)").inc()
        else:
            self.telemetry.counter(
                "inference_trace_cache_misses_total",
                help="decode-loop cache lookups (see "
                     "docs/observability.md)").inc()
        return hit

    def _fail_trace(self, tr, exc: BaseException) -> None:
        """Finish a generation trace as an error (always kept) — a
        crashed generate() must reach /debug/traces, not vanish."""
        if tr is not None and tr.root.end is None:
            tr.root.set("error", type(exc).__name__)
            self.tracer.finish(tr, status="error")

    def _record_generate(self, dt: float) -> None:
        """Per-call latency into the registry (+ model_times when the
        reference-parity profiler is enabled)."""
        if getattr(self, "model_profile_enabled", False):
            self._model_times.append(dt)   # keep model_times 1:1 w/ calls
        self.telemetry.histogram(
            "inference_generate_seconds",
            help="generate()/generate_speculative() call wall time"
        ).observe(dt)
        self.telemetry.counter("inference_generate_calls_total",
                               help="generation calls").inc()

    # ------------------------------------------------------------ setup

    def _build_mesh(self) -> Optional[Mesh]:
        tp = self.config.tp_size
        ep = (self.config.moe.ep_size
              if self.model_config.num_experts > 0 else 1)
        sp = self.config.seq_parallel_size
        if tp <= 1 and ep <= 1 and sp <= 1:
            return None
        devs = jax.devices()
        if len(devs) < tp * ep * sp:
            raise ValueError(f"tp_size={tp} * ep_size={ep} * "
                             f"sp_size={sp} but only {len(devs)} devices")
        # expert outermost (EP all-to-alls are per-MoE-layer), seq next
        # (per-layer attention reductions), TP innermost (per-GEMM
        # allreduces want the tightest ICI)
        return Mesh(np.asarray(devs[:ep * sp * tp]).reshape(ep, sp, tp),
                    ("expert", "seq", "tensor"))

    def _place_params(self, params):
        dtype = self._act_dtype

        def cast(x):
            # pre-quantized {"q","scale"} nodes pass through untouched —
            # their f32 scales must not downcast to the activation dtype
            if isinstance(x, dict) and "q" in x:
                return x
            x = jnp.asarray(x)
            return x.astype(dtype) if jnp.issubdtype(
                x.dtype, jnp.floating) else x
        params = jax.tree.map(
            cast, params,
            is_leaf=lambda x: isinstance(x, dict) and "q" in x)
        if self._weight_quant:
            # AFTER the activation-dtype cast so scales stay f32
            from deepspeed_tpu.module_inject.quantize import GroupQuantizer
            wq = self.config.quant.weight
            # w8a8 compute flips to per-output-channel scales so the
            # ATTENTION projections take the true-int8 MXU dot as well
            # (row-group scales straddle output heads and force dequant)
            params = GroupQuantizer(
                num_bits=wq.num_bits, group_size=wq.group_size,
                out_mode=self.model_config.int8_compute
                ).quantize_tree(params)
        if self.mesh is None:
            return params
        specs = tp_param_specs(params)
        axes = set(self.mesh.axis_names)

        def filter_spec(sp):
            # drop mesh axes this engine's mesh does not have (e.g. expert
            # specs on a TP-only mesh)
            return P(*((a if a in axes else None) for a in sp))
        return jax.tree.map(
            lambda x, sp: jax.device_put(
                x, NamedSharding(self.mesh, filter_spec(sp))),
            params, specs)

    def _max_out_budget(self, batch: int) -> int:
        """KV-token budget per sequence: explicit max_out_tokens, or —
        with max_out_tokens='auto' — sized from the accelerator's free
        memory at call time (kv_cache.auto_max_tokens, the reference's
        inference_context.h free-HBM workspace behavior). Falls back to
        the 1024 default when the backend reports no memory stats."""
        mo = self.config.max_out_tokens
        if mo != "auto":
            return _round_up(int(mo), 128)
        cfg = self.model_config
        # per-device cache bytes shrink by the model-parallel factor
        # (_make_cache shards kv-heads over `tensor`, S over `seq`)
        shard = 1
        if self.mesh is not None:
            ax = self.mesh.shape
            if "seq" in ax:
                shard *= ax["seq"]
            if "tensor" in ax and cfg.kv_heads % ax["tensor"] == 0:
                shard *= ax["tensor"]
        auto = auto_max_tokens(cfg.n_layer, batch, cfg.kv_heads,
                               cfg.head_dim, dtype=self._act_dtype,
                               shard_factor=shard)
        if auto is None:
            return _round_up(1024, 128)
        return auto

    def _make_cache(self, batch: int, max_seq: int) -> KVCache:
        cache = init_cache(self.model_config.n_layer, batch, max_seq,
                           self.model_config.kv_heads,
                           self.model_config.head_dim,
                           dtype=self._act_dtype)
        if self.mesh is not None:
            # long-context: the S dim shards over the seq axis — GSPMD
            # turns the decode softmax into the shard-local
            # score/logsumexp + cross-shard combine of flash-decoding,
            # so per-chip cache HBM drops by sp_size (beyond the
            # v0.8.0 reference, whose KV cache is single-GPU-resident)
            seq_ax = ("seq" if "seq" in self.mesh.axis_names and
                      self.mesh.shape["seq"] > 1 else None)
            sh = NamedSharding(self.mesh,
                               P(None, None, seq_ax, "tensor", None))
            cache = cache.replace(
                k=jax.device_put(cache.k, sh),
                v=jax.device_put(cache.v, sh))
        return cache

    # ------------------------------------------------------------ API

    def profile_model_time(self, use_cuda_events: bool = True) -> None:
        """Enable per-call model-time collection (reference
        ``profile_model_time``, inference/engine.py:139 — forward hooks +
        cuda events; here a host-synced wall-clock bracket around the
        jitted call). ``use_cuda_events`` is accepted for signature
        parity; the sync is a host transfer either way."""
        del use_cuda_events
        self.model_profile_enabled = True
        if not hasattr(self, "_model_times"):
            self._model_times = []

    def model_times(self) -> list:
        """Collected per-call latencies (seconds); clears on read
        (reference ``model_times``, inference/engine.py:483)."""
        if not getattr(self, "model_profile_enabled", False):
            raise AssertionError("model profiling is not enabled — call "
                                 "profile_model_time() first")
        out, self._model_times = self._model_times, []
        return out

    def forward(self, input_ids, attention_mask=None):
        """Encoder forward (BERT-family) → hidden states, or full-sequence
        logits ``[B, T, V]`` for causal models — matching the reference
        ``InferenceEngine.forward`` (inference/engine.py:495), so callers
        scoring ``logits[:, i]`` port 1:1. ``generate`` keeps the KV-cache
        fast path internally."""
        import time as _time
        t0 = (_time.perf_counter()
              if getattr(self, "model_profile_enabled", False) else None)
        input_ids = jnp.asarray(input_ids, jnp.int32)
        if not self.model_config.pre_layer_norm:
            out = self._encoder_jit(self.params, input_ids=input_ids,
                                    attention_mask=attention_mask)
        else:
            if attention_mask is not None:
                attention_mask = jnp.asarray(attention_mask, jnp.int32)
            out = self._causal_fwd_jit(self.params, input_ids=input_ids,
                                       attention_mask=attention_mask)
        if t0 is not None:
            np.asarray(jax.tree.leaves(out)[0])   # host sync
            self._model_times.append(_time.perf_counter() - t0)
        return out

    __call__ = forward

    def _check_schedulable(self, B: int, max_new_tokens: int) -> None:
        """Shared generate/generate_speculative admission contract."""
        if "max_batch_size" in self.config.model_fields_set and \
                B > self.config.max_batch_size:
            # enforced only when the USER set the knob — the default must
            # not reject batches the per-call KV allocation handles fine
            raise ValueError(
                f"batch {B} exceeds the configured max_batch_size="
                f"{self.config.max_batch_size}")
        if max_new_tokens < self.config.min_out_tokens:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} is below "
                f"min_out_tokens={self.config.min_out_tokens} (reference "
                "inference/engine.py rejects un-schedulable budgets)")

    @staticmethod
    def _assemble_output(ids, lengths, out_np, n_np) -> list:
        """Prompt + generated tokens per row, as lists."""
        return [np.asarray(ids[b, :lengths[b]]).tolist()
                + out_np[b, :int(n_np[b])].tolist()
                for b in range(len(lengths))]

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, num_beams: int = 1,
                 length_penalty: float = 1.0,
                 repetition_penalty: float = 1.0,
                 min_new_tokens: int = 0,
                 eos_token_id: Optional[int] = None,
                 attention_mask=None, seed: int = 0,
                 assistant_model: Optional["InferenceEngine"] = None,
                 ) -> list:
        """Greedy/sampled generation. ``input_ids``: a list of token lists
        (per-row lengths inferred), or a right-padded ``[B, T]`` array — in
        which case pass the HF-style ``attention_mask`` so pad columns are
        not scored as context. Returns a list of token lists.

        Mirrors ``InferenceEngine._generate`` (inference/engine.py:523); the
        per-token hot path is the jitted decode step with a donated cache.
        """
        if self.model_config.head == "none":
            raise ValueError(
                "this model has no LM head (CLIP-style encoder) — use "
                "forward() for hidden states; generate() needs vocabulary "
                "logits")
        if assistant_model is not None:
            # HF assisted-generation spelling of the speculative path
            if (top_k or top_p or num_beams > 1 or min_new_tokens or
                    float(repetition_penalty) != 1.0):
                raise ValueError(
                    "assistant_model composes with plain greedy/sampled "
                    "decoding only (no top-k/top-p/beams/penalties/"
                    "min_new_tokens) — see generate_speculative")
            return self.generate_speculative(
                input_ids, assistant_model, max_new_tokens,
                temperature=temperature, eos_token_id=eos_token_id,
                attention_mask=attention_mask, seed=seed)
        import time as _time
        t0 = _time.perf_counter()
        ids, lengths = _pad_batch(input_ids, attention_mask)
        B, T = ids.shape
        if max_new_tokens <= 0:
            # explicit no-op budget: prompts unchanged (exempt from the
            # schedulability checks below — nothing is being scheduled)
            self._record_generate(_time.perf_counter() - t0)
            return [np.asarray(ids[b, :lengths[b]]).tolist()
                    for b in range(B)]
        self._check_schedulable(B, max_new_tokens)
        need = int(lengths.max()) + max_new_tokens
        budget = self._max_out_budget(B * max(num_beams, 1))
        # geometric cache buckets (128·2^k, clamped to the budget): a
        # spread of prompt lengths reuses O(log) decode-loop traces
        # instead of one per distinct 128-span
        max_seq = _fit_to_budget(need, budget)
        if not max_seq:
            raise ValueError(
                f"prompt + max_new_tokens needs a "
                f"{_round_up(need, 128)}-token KV cache "
                f"but the budget is {budget} tokens "
                f"(max_out_tokens={self.config.max_out_tokens!r}; the "
                "reference sizes its workspace from free HBM, "
                "inference_context.h:124 — set max_out_tokens='auto' for "
                "the same behavior here)")
        # mode validations BEFORE the trace opens (and before any
        # compute dispatches — strictly earlier failure than scoring
        # the prefill first): a refused parameter combination is the
        # caller's error, not a traced request
        if num_beams > 1:
            if float(temperature) > 0.0 or top_k or top_p:
                raise ValueError(
                    "beam search composes with greedy scoring only "
                    "(sampling+beams is not supported, matching HF's "
                    "separate code paths)")
            if float(repetition_penalty) != 1.0 or min_new_tokens:
                raise NotImplementedError(
                    "repetition_penalty/min_new_tokens are wired into "
                    "the greedy/sampled loop, not beam search")
        else:
            if float(repetition_penalty) <= 0.0:
                raise ValueError(
                    "repetition_penalty must be strictly positive (HF "
                    "raises the same); 1.0 disables it")
            if (int(top_k) > 0 or float(top_p) > 0.0) and \
                    float(temperature) <= 0.0:
                raise ValueError(
                    "top_k/top_p are sampling filters — pass "
                    "temperature>0 (HF samples at temperature=1.0 by "
                    "default); temperature=0 means greedy and would "
                    "silently ignore them")
        # two-level request trace (telemetry/tracing.py): root +
        # dispatch/fetch children. Generation stays ONE host sync — the
        # children time the dispatch intervals and the final fetch (the
        # device wait), not per-phase block_until_ready barriers. A
        # failure past this point finishes the trace as an error
        # (always kept), so crashed generations reach /debug/traces.
        tr = None
        if self.tracer is not None:
            tr = self.tracer.start_trace(
                "generate", rows=B, max_new_tokens=max_new_tokens,
                prompt_tokens=int(lengths.sum()))
        try:
            if num_beams > 1:
                # tiled prefill: every beam shares the prefix; one pass
                # per beam is wasteful but keeps one prefill program
                # for all modes
                tiled_ids = np.repeat(ids, num_beams, axis=0)
                tiled_len = np.repeat(lengths, num_beams, axis=0)
                cache = self._make_cache(B * num_beams, max_seq)
                sp = tr.begin("dispatch", beams=num_beams) if tr else None
                logits, cache = self._prefill_jit(
                    self.params, input_ids=jnp.asarray(tiled_ids),
                    lengths=jnp.asarray(tiled_len), cache=cache)
                loop = self._beam_loop(max_new_tokens, num_beams)
                out_buf, n_gen, _ = loop(
                    self.params, logits, cache, jnp.asarray(lengths),
                    jnp.int32(-1 if eos_token_id is None
                              else eos_token_id),
                    jnp.float32(length_penalty))
                if tr:
                    tr.end_span(sp)
                    sp = tr.begin("fetch")
                out_np = np.asarray(out_buf)
                n_np = np.asarray(n_gen)
                if tr:
                    tr.end_span(sp)
                    self.tracer.finish(tr)
                self._record_generate(_time.perf_counter() - t0)
                return self._assemble_output(ids, lengths, out_np, n_np)
            cache = self._make_cache(B, max_seq)
            sp = tr.begin("prefill_dispatch", cache_len=max_seq) if tr \
                else None
            logits, cache = self._prefill_jit(
                self.params, input_ids=jnp.asarray(ids),
                lengths=jnp.asarray(lengths), cache=cache)
            if tr:
                tr.end_span(sp)
            rep_on = float(repetition_penalty) != 1.0
            loop = self._generate_loop(max_new_tokens,
                                       float(temperature) > 0.0,
                                       int(top_k) > 0, float(top_p) > 0.0,
                                       rep_on)
            # presence mask over the PROMPT (HF's repetition penalty
            # scores every prior token, context included); pads (beyond
            # lengths) and the loop's generated tokens extend it on
            # device
            if rep_on:
                V = self.model_config.vocab_size
                presence = np.zeros((B, V), bool)
                for b in range(B):
                    presence[b, np.asarray(ids[b, :lengths[b]])] = True
                presence = jnp.asarray(presence)
            else:
                presence = jnp.zeros((B, 1), bool)   # unused placeholder
            sp = tr.begin("decode_dispatch") if tr else None
            out_buf, n_gen, _ = loop(
                self.params, logits, cache, jax.random.PRNGKey(seed),
                jnp.float32(temperature), jnp.int32(top_k),
                jnp.float32(top_p),
                jnp.int32(-1 if eos_token_id is None else eos_token_id),
                presence, jnp.float32(repetition_penalty),
                jnp.int32(min_new_tokens))
            if tr:
                tr.end_span(sp)
                sp = tr.begin("fetch")
            # ONE host sync per generation (the reference built CUDA
            # graphs to kill per-token launch overhead, inference/
            # engine.py:454-473; a per-token device->host fetch is the
            # TPU analog).
            out_np = np.asarray(out_buf)
            n_np = np.asarray(n_gen)
            if tr:
                tr.end_span(sp)
                self.tracer.finish(tr)
            self._record_generate(_time.perf_counter() - t0)
            return self._assemble_output(ids, lengths, out_np, n_np)
        except BaseException as e:  # noqa: BLE001 — recorded, re-raised
            self._fail_trace(tr, e)
            raise

    def generate_speculative(self, input_ids,
                             draft: Optional["InferenceEngine"] = None,
                             max_new_tokens: int = 32,
                             draft_tokens: int = 4, *,
                             temperature: float = 0.0,
                             eos_token_id: Optional[int] = None,
                             attention_mask=None, seed: int = 0) -> list:
        """Speculative decoding with a smaller draft engine. Each round
        the draft proposes ``draft_tokens - 1`` tokens sequentially; the
        target scores the whole candidate chunk in ONE ``decode_chunk``
        forward and commits 1 to ``draft_tokens`` tokens per forward.

        ``temperature == 0``: greedy acceptance — IDENTICAL output to
        greedy ``generate``. ``temperature > 0``: rejection-sampling
        acceptance (Leviathan et al. / Chen et al., public technique):
        proposal ``d_i`` accepted with prob ``min(1, p_t(d_i)/p_d(d_i))``,
        the first rejection resampled from ``norm(max(p_t - p_d, 0))`` —
        the committed stream is distributed EXACTLY like sampling from
        the target alone, at temperature ``temperature``. top-k/top-p
        filters are not supported on the speculative path.

        ``draft=None``: PROMPT-LOOKUP decoding (draft-model-free, greedy
        only) — proposals are the ``draft_tokens - 1`` tokens that
        followed the most recent earlier occurrence of the current
        bigram in the row's own prompt+generated history. Zero extra
        model cost per proposal; repetitive continuations (code, quoted
        spans, structured text) verify several tokens per target
        forward, and the output is still exactly greedy.

        TPU-native shape: the whole accept/rollback loop is one jitted
        ``lax.while_loop`` (one host sync per generation); rollback is
        free because the static KV cache masks by per-row ``lengths``, so
        rejected positions are simply never advanced over. Beyond the
        reference (strictly one-token decode).
        """
        import time as _time
        t0 = _time.perf_counter()
        if draft_tokens < 2:
            raise ValueError(f"draft_tokens must be >= 2, got "
                             f"{draft_tokens} (1 draft proposal minimum)")
        if draft is not None:
            check_draft_compat(self, draft)
        elif self.model_config.head == "none":
            raise ValueError("speculative decoding needs LM heads on "
                             "both engines")
        if draft is None and float(temperature) > 0.0:
            raise NotImplementedError(
                "prompt-lookup speculative decoding (draft=None) is "
                "greedy-only: its proposals are deterministic, so "
                "rejection sampling degenerates — pass a draft engine "
                "for sampled speculation")
        ids, lengths = _pad_batch(input_ids, attention_mask)
        B, T = ids.shape
        if max_new_tokens <= 0:
            self._record_generate(_time.perf_counter() - t0)
            return [np.asarray(ids[b, :lengths[b]]).tolist()
                    for b in range(B)]
        self._check_schedulable(B, max_new_tokens)   # same as generate
        K = int(draft_tokens)
        # margin: the draft runs K appends past the last committed token,
        # and the final round may overshoot max_new by up to K
        need = int(lengths.max()) + max_new_tokens + 2 * K
        max_seq = None
        for eng in ((self,) if draft is None else (self, draft)):
            budget = eng._max_out_budget(B)
            fit = _fit_to_budget(need, budget)
            if not fit:
                raise ValueError(
                    f"prompt + max_new_tokens + draft margin needs a "
                    f"{_round_up(need, 128)}-token KV cache but the "
                    f"{'draft' if eng is draft else 'target'} budget is "
                    f"{budget} tokens (max_out_tokens="
                    f"{eng.config.max_out_tokens!r})")
            max_seq = fit if max_seq is None else min(max_seq, fit)
        cache_t = self._make_cache(B, max_seq)
        logits_t, cache_t = self._prefill_jit(
            self.params, input_ids=jnp.asarray(ids),
            lengths=jnp.asarray(lengths), cache=cache_t)
        eos_arg = jnp.int32(-1 if eos_token_id is None else eos_token_id)
        if draft is None:
            # prompt-lookup: history buffer instead of a draft cache
            hist = jnp.zeros((B, T + max_new_tokens + 2 * K), jnp.int32)
            hist = hist.at[:, :T].set(jnp.asarray(ids))
            loop = self._lookup_loop(max_new_tokens, K)
            out_buf, n_gen, rounds, _ = loop(
                self.params, logits_t, cache_t, hist,
                jnp.asarray(lengths), eos_arg)
        else:
            cache_d = draft._make_cache(B, max_seq)
            _, cache_d = draft._prefill_jit(
                draft.params, input_ids=jnp.asarray(ids),
                lengths=jnp.asarray(lengths), cache=cache_d)
            loop = self._speculative_loop(
                draft, max_new_tokens, K,
                sampled=float(temperature) > 0.0)
            out_buf, n_gen, rounds, _, _ = loop(
                self.params, draft.params, logits_t, cache_t, cache_d,
                eos_arg, jax.random.PRNGKey(seed),
                jnp.float32(max(temperature, 1e-6)))
        out_np = np.asarray(out_buf)[:, :max_new_tokens]
        n_np = np.minimum(np.asarray(n_gen), max_new_tokens)
        # acceptance telemetry: tokens-per-target-forward is THE number
        # that decides whether a draft pays off (rounds counts verify
        # forwards; +1 for the prefill token)
        total = int(n_np.sum())
        self.last_speculative_stats = {
            "rounds": int(rounds), "tokens": total,
            "draft": "prompt-lookup" if draft is None else "model",
            "tokens_per_round": round(total / max(int(rounds), 1), 3)}
        self._record_generate(_time.perf_counter() - t0)
        return self._assemble_output(ids, lengths, out_np, n_np)

    def _lookup_loop(self, max_new_tokens: int, K: int):
        """Jitted prompt-lookup speculative loop: proposals come from the
        most recent earlier occurrence of the current BIGRAM in the
        row's own history (prompt + generated), verified exactly like
        draft proposals — greedy only, no second model, no draft cache."""
        key = ("spec-lookup", max_new_tokens, K)
        hit = self._loop_cache_get(key)
        if hit is not None:
            return hit
        cfg_t, mesh_t = self.model_config, self.mesh

        def run(params_t, logits_t, cache_t, hist, hlen, eos):
            B, S = hist.shape
            ar = jnp.arange(B)
            cur = jnp.argmax(logits_t, -1).astype(jnp.int32)  # token 0
            hist = hist.at[ar, hlen].set(cur)
            hlen = hlen + 1
            out = jnp.zeros((B, max_new_tokens + K), jnp.int32)
            out = out.at[:, 0].set(cur)
            n_gen = jnp.ones((B,), jnp.int32)
            done = cur == eos

            def cond(c):
                done, n_gen = c[3], c[4]
                return jnp.any(~done & (n_gen < max_new_tokens))

            def body(c):
                cur, cache_t, hist, done, n_gen, out, rounds, hlen = c
                base_t = cache_t.lengths

                # 1) propose (shared rule, inference/speculation.py):
                # latest j with hist[j:j+2] == the current bigram
                # (strictly before it), continuation as proposals
                props = lookup_proposals(hist, hlen, cur, K)  # [B, K-1]

                # 2) target verifies [cur, props] in one forward
                chunk = jnp.concatenate([cur[:, None], props], axis=1)
                lg_t, cache_t = decode_chunk(params_t, cfg_t, chunk,
                                             cache_t, mesh=mesh_t)
                t_toks = jnp.argmax(lg_t, -1).astype(jnp.int32)  # [B, K]
                m, correction, committed = _greedy_accept(t_toks, props, K)
                iota = jnp.arange(K)[None, :]

                # 3) shared commit + history append (hist leads the cache
                # by one pending token: it also receives the correction)
                out, n_gen, done, adv, active = _commit_speculative_block(
                    committed, m, done, n_gen, out, eos, K,
                    max_new_tokens)
                cache_t = cache_t.replace(lengths=base_t + adv)
                hcols = jnp.clip(hlen[:, None] + iota, 0, S - 1)
                hmask = (iota <= m[:, None]) & active[:, None]
                hist = hist.at[ar[:, None], hcols].set(
                    jnp.where(hmask, committed, hist[ar[:, None], hcols]))
                hlen = hlen + adv
                cur = jnp.where(active, correction[:, 0], cur)
                return (cur, cache_t, hist, done, n_gen, out, rounds + 1,
                        hlen)

            carry = (cur, cache_t, hist, done, n_gen, out, jnp.int32(0),
                     hlen)
            carry = jax.lax.while_loop(cond, body, carry)
            # final cache returned (and dropped) so donation can alias
            return carry[5], carry[4], carry[6], carry[1]

        loop = watched_jit(run, name="infer_lookup_loop",
                           registry=self.telemetry,
                           donate_argnames=("cache_t",))
        self._gen_loops[key] = loop
        return loop

    def _speculative_loop(self, draft: "InferenceEngine",
                          max_new_tokens: int, K: int,
                          sampled: bool = False):
        """Jitted draft→verify→commit loop (see generate_speculative)."""
        key = ("spec", id(draft), max_new_tokens, K, sampled)
        # the cache entry holds a strong reference to the draft: id() is
        # only unique while the object lives, so a GC'd draft's reused id
        # must not serve a stale loop closed over its config/mesh
        hit = self._loop_cache_get(key)
        if hit is not None:
            return hit[0]
        cfg_t, cfg_d = self.model_config, draft.model_config
        mesh_t, mesh_d = self.mesh, draft.mesh

        def run(params_t, params_d, logits_t, cache_t, cache_d, eos, rng,
                temp):
            B = logits_t.shape[0]
            rng, sub = jax.random.split(rng)
            if sampled:   # token 0 from the prefill logits
                cur = jax.random.categorical(
                    sub, logits_t / temp, -1).astype(jnp.int32)
            else:
                cur = jnp.argmax(logits_t, -1).astype(jnp.int32)
            out = jnp.zeros((B, max_new_tokens + K), jnp.int32)
            out = out.at[:, 0].set(cur)
            n_gen = jnp.ones((B,), jnp.int32)
            done = cur == eos

            def cond(c):
                done, n_gen = c[3], c[4]
                return jnp.any(~done & (n_gen < max_new_tokens))

            def body(c):
                cur, cache_t, cache_d, done, n_gen, out, rounds, rng = c
                base_t = cache_t.lengths   # committed context length
                base_d = cache_d.lengths

                # 1) draft proposes K-1 tokens; the K-th step only backfills
                # d_{K-1}'s k/v so a full accept leaves no cache hole
                def dstep(carry, _):
                    tok, cd, r = carry
                    lg, cd = decode_step(params_d, cfg_d, tok, cd,
                                         mesh=mesh_d)
                    r, s = jax.random.split(r)
                    if sampled:
                        nxt = jax.random.categorical(
                            s, lg / temp, -1).astype(jnp.int32)
                        pd = jax.nn.softmax(lg / temp, -1)
                    else:
                        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                        pd = jnp.zeros((B, 1), jnp.float32)  # unused
                    return (nxt, cd, r), (nxt, pd)

                rng, sub = jax.random.split(rng)
                (_, cache_d, _), (drafts, pd) = jax.lax.scan(
                    dstep, (cur, cache_d, sub), None, length=K)
                drafts = jnp.swapaxes(drafts, 0, 1)      # [B, K] d1..dK
                pd = jnp.swapaxes(pd, 0, 1)              # [B, K, V|1]

                # 2) target verifies [cur, d1..d_{K-1}] in one forward
                chunk = jnp.concatenate([cur[:, None], drafts[:, :K - 1]],
                                        axis=1)          # [B, K]
                lg_t, cache_t = decode_chunk(params_t, cfg_t, chunk,
                                             cache_t, mesh=mesh_t)
                iota = jnp.arange(K)[None, :]
                if sampled:
                    # rejection sampling (speculative-decoding paper):
                    # position i's target dist pt_i pairs with proposal
                    # d_{i+1} ~ pd_i; accept while
                    # u_i < pt_i(d_{i+1}) / pd_i(d_{i+1})
                    pt = jax.nn.softmax(lg_t / temp, -1)  # [B, K, V]
                    props = drafts[:, :K - 1]             # [B, K-1]
                    p_t_at = jnp.take_along_axis(
                        pt[:, :K - 1], props[:, :, None], 2)[..., 0]
                    p_d_at = jnp.take_along_axis(
                        pd[:, :K - 1], props[:, :, None], 2)[..., 0]
                    rng, sub = jax.random.split(rng)
                    u = jax.random.uniform(sub, (B, K - 1))
                    accept = u * jnp.maximum(p_d_at, 1e-30) < p_t_at
                    m = jnp.argmin(
                        jnp.concatenate(
                            [accept, jnp.zeros((B, 1), bool)], 1).astype(
                                jnp.int32), axis=1)      # 0..K-1
                    # correction dist at position m: residual
                    # norm(max(pt-pd, 0)) after a rejection; raw pt at
                    # the bonus position (m == K-1, nothing rejected)
                    resid = jnp.maximum(
                        pt[:, :K - 1] - pd[:, :K - 1], 0.0)
                    dists = jnp.concatenate(
                        [resid, pt[:, K - 1:]], axis=1)   # [B, K, V]
                    dist_m = jnp.take_along_axis(
                        dists, m[:, None, None], 1)[:, 0]  # [B, V]
                    rng, sub = jax.random.split(rng)
                    correction = jax.random.categorical(
                        sub, jnp.log(dist_m + 1e-30), -1).astype(
                            jnp.int32)[:, None]
                else:
                    t_toks = jnp.argmax(lg_t, -1).astype(jnp.int32)
                    m, correction, committed = _greedy_accept(
                        t_toks, drafts[:, :K - 1], K)
                if sampled:
                    # committed tokens: d1..dm then the correction
                    committed = jnp.where(iota < m[:, None], drafts,
                                          correction)    # [B, K]
                out, n_gen, done, adv, active = _commit_speculative_block(
                    committed, m, done, n_gen, out, eos, K,
                    max_new_tokens)
                # 4) cache bookkeeping: context gains [cur, d1..dm] on
                # active rows (the correction becomes the next `cur`);
                # draft rolls back from its K appends to the same point
                cache_t = cache_t.replace(lengths=base_t + adv)
                cache_d = cache_d.replace(lengths=base_d + adv)
                cur = jnp.where(active, correction[:, 0], cur)
                return (cur, cache_t, cache_d, done, n_gen, out,
                        rounds + 1, rng)

            carry = (cur, cache_t, cache_d, done, n_gen, out,
                     jnp.int32(0), rng)
            carry = jax.lax.while_loop(cond, body, carry)
            # final caches returned (and dropped by the caller) so the
            # donated inputs can actually alias an output — same pattern
            # as _generate_loop
            return carry[5], carry[4], carry[6], carry[1], carry[2]

        loop = watched_jit(run, name="infer_speculative_loop",
                           registry=self.telemetry,
                           donate_argnames=("cache_t", "cache_d"))
        # one draft at a time: entries for other draft ids are evicted so
        # a rotated-out draft (and its weights) can be garbage-collected
        # instead of pinning device memory for the target's lifetime
        for k in [k for k in self._gen_loops
                  if k[0] == "spec" and k[1] != id(draft)]:
            del self._gen_loops[k]
        self._gen_loops[key] = (loop, draft)
        return loop

    def _beam_loop(self, max_new_tokens: int, num_beams: int):
        """Jitted beam search (the reference serves beams through HF's
        patched ``generate`` over its fused forward, inference/engine.py:
        523; here the whole search is ONE compiled program). Finished
        beams freeze in place (t5x-style) — identical to HF's beam search
        whenever no beam ends before the token budget, and a documented
        simplification of the hypothesis pool when one does."""
        key = ("beam", max_new_tokens, num_beams)
        loop = self._loop_cache_get(key)
        if loop is not None:
            return loop
        cfg = self.model_config
        mesh = self.mesh
        nb = num_beams

        def run(params, logits, cache, prompt_lens, eos, length_penalty):
            Bnb = logits.shape[0]
            B = Bnb // nb
            V = logits.shape[-1]
            logp0 = jax.nn.log_softmax(
                logits.astype(jnp.float32), -1).reshape(B, nb, V)
            # all beams start from the same prefix: seed with the top-nb
            # DISTINCT first tokens of beam 0's distribution
            scores, tok = jax.lax.top_k(logp0[:, 0], nb)     # [B, nb]
            out = jnp.zeros((B, nb, max_new_tokens), jnp.int32)
            out = out.at[:, :, 0].set(tok)
            finished = tok == eos
            n_gen = jnp.ones((B, nb), jnp.int32)

            def cond(c):
                step, _, _, _, finished, _, _ = c
                return (step < max_new_tokens) & \
                    jnp.logical_not(finished.all())

            def body(c):
                step, tok, cache, scores, finished, out, n_gen = c
                lg, cache = decode_step(params, cfg, tok.reshape(-1),
                                        cache, mesh=mesh)
                logp = jax.nn.log_softmax(
                    lg.astype(jnp.float32), -1).reshape(B, nb, V)
                # frozen-finished: a finished beam may only emit pad(0)
                # at unchanged score
                pad_row = jnp.full((V,), -jnp.inf).at[0].set(0.0)
                logp = jnp.where(finished[:, :, None], pad_row, logp)
                cand = scores[:, :, None] + logp            # [B, nb, V]
                scores, flat = jax.lax.top_k(cand.reshape(B, nb * V), nb)
                parent = flat // V                           # [B, nb]
                tok = (flat % V).astype(jnp.int32)
                flat_parent = (jnp.arange(B)[:, None] * nb +
                               parent).reshape(-1)
                cache = cache.replace(
                    k=cache.k[:, flat_parent], v=cache.v[:, flat_parent],
                    lengths=cache.lengths[flat_parent])
                out = jnp.take_along_axis(out, parent[:, :, None], axis=1)
                finished = jnp.take_along_axis(finished, parent, axis=1)
                n_gen = jnp.take_along_axis(n_gen, parent, axis=1)
                out = out.at[:, :, step].set(jnp.where(finished, 0, tok))
                n_gen = n_gen + jnp.where(finished, 0, 1)
                finished = finished | (tok == eos)
                return step + 1, tok, cache, scores, finished, out, n_gen

            carry = (jnp.int32(1), tok, cache, scores, finished, out,
                     n_gen)
            step, tok, cache, scores, finished, out, n_gen = \
                jax.lax.while_loop(cond, body, carry)
            # HF convention (BeamSearchScorer): rank by
            # score / full_len**penalty, full_len = prompt + generated
            full_len = (prompt_lens[:, None] + n_gen).astype(jnp.float32)
            norm = scores / (full_len ** length_penalty)
            best = jnp.argmax(norm, axis=1)                  # [B]
            sel = jnp.take_along_axis(
                out, best[:, None, None], axis=1)[:, 0]      # [B, T]
            n_sel = jnp.take_along_axis(n_gen, best[:, None], axis=1)[:, 0]
            return sel, n_sel, cache

        loop = watched_jit(run, name="infer_beam_loop",
                           registry=self.telemetry,
                           donate_argnames=("cache",))
        self._gen_loops[key] = loop
        return loop

    def _generate_loop(self, max_new_tokens: int, sampled: bool,
                       top_k_on: bool, top_p_on: bool = False,
                       rep_on: bool = False):
        """Compile (and cache) the whole decode loop as ONE program: a
        ``lax.while_loop`` over the donated KV cache with on-device
        sampling and EOS bookkeeping. Early-exits when every row is done.
        Only structure is baked into the compile key (length, greedy vs
        sampled, top-k/top-p/repetition on/off); temperature/top_k/eos/
        penalties ride as traced scalars so sweeps don't recompile."""
        key = (max_new_tokens, sampled, top_k_on, top_p_on, rep_on)
        loop = self._loop_cache_get(key)
        if loop is not None:
            return loop
        cfg = self.model_config
        mesh = self.mesh  # MoE: decode hot path needs the EP constraint too

        def adjust(lg, presence, rep, min_left, eos):
            if rep_on:
                # HF RepetitionPenaltyLogitsProcessor: seen tokens'
                # logits divide (positive) or multiply (negative) by p
                pen = jnp.where(lg > 0, lg / rep, lg * rep)
                lg = jnp.where(presence, pen, lg)
            # min_new_tokens: suppress EOS while the floor is unmet
            # (HF MinNewTokensLengthLogitsProcessor); eos==-1 disables
            lg = jnp.where(
                (min_left > 0) & (eos >= 0) &
                (jnp.arange(lg.shape[-1])[None, :] == eos),
                -jnp.inf, lg)
            return lg

        def select(lg, rng, temperature, top_k, top_p):
            if not sampled:
                return jnp.argmax(lg, -1).astype(jnp.int32)
            lg = lg / temperature
            if top_k_on:
                kth = jnp.take_along_axis(
                    jnp.sort(lg, -1), lg.shape[-1] - top_k[None, None],
                    axis=-1)
                lg = jnp.where(lg < kth, -1e30, lg)
            if top_p_on:
                # nucleus sampling: keep the smallest prefix of the
                # descending-probability ordering whose mass >= top_p
                srt = jnp.sort(lg, -1)[..., ::-1]
                probs = jax.nn.softmax(srt, -1)
                cum = jnp.cumsum(probs, -1)
                keep = cum - probs < top_p[None, None]  # always keep top-1
                cutoff = jnp.max(jnp.where(keep, srt, -jnp.inf), -1,
                                 keepdims=True)
                lg = jnp.where(lg < cutoff, -1e30, lg)
            return jax.random.categorical(rng, lg, -1).astype(jnp.int32)

        def run(params, logits, cache, rng, temperature, top_k, top_p,
                eos, presence, rep, min_new):
            B = logits.shape[0]
            # token 0 comes from the prefill logits; each loop iteration
            # decodes the previous token first, so the final token never
            # pays a wasted trailing decode_step. eos == -1 disables EOS
            # stopping (token ids are non-negative).
            rng, sub = jax.random.split(rng)
            logits = adjust(logits, presence, rep, min_new, eos)
            tok = select(logits, sub, temperature, top_k, top_p)
            if rep_on:
                presence = presence.at[jnp.arange(B), tok].set(True)
            out = jnp.zeros((B, max_new_tokens), jnp.int32).at[:, 0].set(tok)
            done = tok == eos
            n_gen = jnp.ones((B,), jnp.int32)

            def cond(c):
                step = c[0]
                done = c[3]
                return (step < max_new_tokens) & jnp.logical_not(done.all())

            def body(c):
                step, tok, cache, done, out, n_gen, rng, presence = c
                lg, cache = decode_step(params, cfg, tok, cache, mesh=mesh)
                rng, sub = jax.random.split(rng)
                lg = adjust(lg, presence, rep, min_new - step, eos)
                nxt = select(lg, sub, temperature, top_k, top_p)
                if rep_on:
                    presence = presence.at[jnp.arange(B), nxt].set(True)
                out = out.at[:, step].set(jnp.where(done, 0, nxt))
                n_gen = n_gen + jnp.where(done, 0, 1)
                done = done | (nxt == eos)
                return (step + 1, nxt, cache, done, out, n_gen, rng,
                        presence)

            carry = (jnp.int32(1), tok, cache, done, out, n_gen, rng,
                     presence)
            carry = jax.lax.while_loop(cond, body, carry)
            # the final cache is returned (and dropped by the caller) so
            # the donated input cache can actually alias an output
            return carry[4], carry[5], carry[2]

        loop = watched_jit(run, name="infer_generate_loop",
                           registry=self.telemetry,
                           donate_argnames=("cache",))
        self._gen_loops[key] = loop
        return loop


def _pad_batch(input_ids, attention_mask=None):
    """Right-pad to a geometric bucket (``_bucket``): varying prompt
    lengths land on O(log) prefill shapes instead of one per 128-span."""
    if isinstance(input_ids, (list, tuple)):
        lengths = np.asarray([len(r) for r in input_ids], np.int32)
        T = _bucket(max(int(lengths.max()), 1))
        ids = np.zeros((len(input_ids), T), np.int32)
        for i, row in enumerate(input_ids):
            ids[i, :len(row)] = row
        return ids, lengths
    ids = np.asarray(input_ids, np.int32)
    if attention_mask is not None:
        lengths = np.asarray(attention_mask).sum(-1).astype(np.int32)
    else:
        lengths = np.full((ids.shape[0],), ids.shape[1], np.int32)
    if ids.shape[1] != _bucket(ids.shape[1]):
        padded = np.zeros((ids.shape[0], _bucket(ids.shape[1])), np.int32)
        padded[:, :ids.shape[1]] = ids
        ids = padded
    return ids, lengths


def save_serving_checkpoint(engine: InferenceEngine, path: str) -> None:
    """Write the CONVERTED (and possibly int8-quantized) serving state to
    disk — the reference's ``save_mp_checkpoint_path`` (init_inference can
    persist the injected/re-sharded model so later servers skip policy
    conversion and quantization). Layout:

        <path>/serving_config.json   InferenceTransformerConfig fields
        <path>/serving.safetensors   flat '/'-joined param leaves
    """
    import json
    import os

    import dataclasses as dc
    from safetensors.numpy import save_file

    from deepspeed_tpu.utils.tree import flatten_with_names

    os.makedirs(path, exist_ok=True)
    cfg = dc.asdict(engine.model_config)
    cfg["dtype"] = str(jnp.dtype(engine.model_config.dtype))
    for k, v in list(cfg.items()):
        if isinstance(v, tuple):
            cfg[k] = list(v)
    with open(os.path.join(path, "serving_config.json"), "w") as f:
        json.dump(cfg, f, indent=1)
    flat = {k: np.asarray(jax.device_get(v))
            for k, v in flatten_with_names(engine.params).items()}
    save_file(flat, os.path.join(path, "serving.safetensors"))


def load_serving_checkpoint(path: str,
                            config: Optional[DeepSpeedInferenceConfig]
                            = None) -> InferenceEngine:
    """Rebuild an :class:`InferenceEngine` from ``save_serving_checkpoint``
    output — no policy conversion, no re-quantization (int8 q/scale leaves
    reload as stored)."""
    import json
    import os

    from safetensors import safe_open

    with open(os.path.join(path, "serving_config.json")) as f:
        raw = json.load(f)
    raw["dtype"] = jnp.dtype(raw["dtype"]).type
    for k in ("local_windows", "moe_layers"):
        if raw.get(k) is not None:
            raw[k] = tuple(raw[k])
    model_cfg = InferenceTransformerConfig(**raw)

    # rebuild the nested tree from '/'-joined names
    tree: Dict[str, Any] = {}
    with safe_open(os.path.join(path, "serving.safetensors"),
                   framework="numpy") as h:
        for name in h.keys():
            parts = name.split("/")
            node = tree
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = h.get_tensor(name)

    def listify(node):
        if isinstance(node, dict):
            if node and all(k.isdigit() for k in node):
                return [listify(node[str(i)]) for i in range(len(node))]
            return {k: listify(v) for k, v in node.items()}
        return jnp.asarray(node)
    params = listify(tree)
    return InferenceEngine((model_cfg, params), config)
