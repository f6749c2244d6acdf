#!/usr/bin/env python3
"""LongCat-Flash's serving programs alone at the decode-batch cell's
sizes (its configuration file's bf16 weights, 256 slots, a pool of 2049
blocks of 128, tables of 8): what the rider program gives and what it
costs, beside the two programs it replaces.

    chiprun -- python3 scripts/longcat_admit_programs.py [--seed N]
    JAX_PLATFORMS=cpu python3 scripts/longcat_admit_programs.py --tiny

The pool is filled by the programs the comparison does not judge: every
slot is prefilled by ``paged_prefill`` and decoded ``--grow`` steps by
``paged_decode_step`` on its own greedy tokens, so the rows attended are
rows a server would hold. Then, for each bucket (128, 256):

- **parity**: ``paged_decode_admit`` with every slot but one decoding
  and a prompt admitted into that one, against ``paged_decode_step`` over
  the same rows followed by ``paged_prefill`` of the same prompt, from
  the same pool: the greedy tokens, the largest logit gap over the
  decode rows and on the rider's row (beside the margin between the two
  best logits of the rows whose token differs, if any), and the pool
  (every block but the null block), lengths and tables afterwards. The
  same for the program with NO row decoding against ``paged_prefill``.
  Two bfloat16 programs differ by their roundings, so both are also held
  to the cell's plain float32 reference (``benchmark/lib/
  reference_longcat.py`` over everything a slot has been fed) by the
  cell's own statistic and limit, on ``--ref-rows`` decode rows and the
  rider's row.
- **time**: host clock over ``--calls`` chained calls of each program,
  ended by a transfer of the last output (``prompt``: the live rows of
  the bucket, which the expert layers' work follows).

One JSON line a reading (``{"parity": ...}``, ``{"program": ...}``); a
time from a CPU run (``--tiny``) is not a device number."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import harness  # noqa: E402
from deepspeed_tpu.inference.kv_cache import (  # noqa: E402
    LatentPagedCache, init_latent_paged_cache)
from deepspeed_tpu.model_implementations import (  # noqa: E402
    longcat_flash as lf)
from deepspeed_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache)

TINY_MODEL = dict(
    hidden_size=64, num_layers=2, num_attention_heads=4,
    ffn_hidden_size=128, expert_ffn_hidden_size=32, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=8, zero_expert_num=4, moe_topk=3,
    experts_held=[2, 6], vocab_size=512, dtype="float32")


def say(**line):
    print(json.dumps(line), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--grow", type=int, default=None,
                    help="decode steps that fill the pool (400; tiny 20)")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--ref-rows", type=int, default=64,
                    help="decode rows also held to the float32 reference")
    ap.add_argument("--tiny", action="store_true",
                    help="a toy size in float32, for a CPU rehearsal")
    args = ap.parse_args(argv)
    enable_compile_cache()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longcat-flash-ep32-serve.json")) as fh:
        conf = json.load(fh)
    model = conf["model"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longcat-decode-batch.json")) as fh:
        tolerance = json.load(fh)["check"]["tie_tolerance"]
    if args.tiny:
        model.update(TINY_MODEL)
    fam = harness.load_family("longcat_flash")
    cfg, params = fam.serve_model(model, args.seed)
    S, BS, MB = (8, 128, 8) if args.tiny else (256, 128, 8)
    NB = 1 + S * MB
    grow = args.grow if args.grow is not None else (20 if args.tiny else 400)
    buckets = (128, 256)
    rng = np.random.default_rng(args.seed)
    vocab = model["vocab_size"]

    decode = jax.jit(lambda p, t, c, a: lf.paged_decode_step(p, cfg, t, c, a),
                     donate_argnums=(2,))
    prefill = jax.jit(
        lambda p, i, n, c, s: lf.paged_prefill(p, cfg, i, n, c, s),
        donate_argnums=(3,))
    admit = jax.jit(
        lambda p, t, c, a, i, n, s: lf.paged_decode_admit(
            p, cfg, t, c, a, i, n, s), donate_argnums=(2,))

    def prompt(T):
        n = int(rng.integers(T // 2 + 1, T + 1))
        ids = np.zeros((1, T), np.int32)
        ids[0, :n] = rng.integers(1, vocab, size=n)
        return jnp.asarray(ids), jnp.asarray([n], jnp.int32)

    # ---- a pool a server would hold, made by prefill + decode alone
    tables = 1 + np.arange(S * MB, dtype=np.int32).reshape(S, MB)
    cache = init_latent_paged_cache(
        cfg.attentions, S, NB, BS, MB, cfg.latent_width,
        aux_shape=cfg.aux_shape, dtype=cfg.dtype)
    cache = cache.replace(block_tables=jnp.asarray(tables))
    fed = []                        # a slot: every token it has been fed
    first = np.zeros((S,), np.int32)
    for s in range(S):
        ids, n = prompt(buckets[s % 2])
        logits, cache = prefill(params, ids, n, cache, jnp.int32(s))
        first[s] = int(jnp.argmax(logits[0]))
        fed.append(list(np.asarray(ids)[0, :int(n[0])]))
    tokens, everyone = jnp.asarray(first), jnp.ones((S,), bool)
    for _ in range(grow):
        for s, t in enumerate(np.asarray(tokens)):
            fed[s].append(int(t))
        logits, cache = decode(params, tokens, cache, everyone)
        tokens = jnp.argmax(logits, -1).astype(jnp.int32)
    for s, t in enumerate(np.asarray(tokens)):
        fed[s].append(int(t))       # what the compared step feeds
    lengths = np.asarray(cache.lengths)
    say(state={"slots": S, "blocks": NB, "grown": grow,
               "contexts": [int(lengths.min()), int(lengths.max())],
               "device": jax.devices()[0].device_kind})
    held = {"rows": [np.asarray(r) for r in cache.rows],
            "lengths": lengths, "aux": np.asarray(cache.aux)}
    del cache

    def state(slot=None, installed=True, empty=False):
        """The held pool on the device again; ``slot`` free (length 0;
        its table row the null block's, or its blocks where the
        admission has ``installed`` them); every length 0 for an
        ``empty`` server."""
        t, n = tables.copy(), held["lengths"].copy()
        if slot is not None:
            n[slot] = 0
            if not installed:
                t[slot] = 0
        if empty:
            n[:] = 0
        return LatentPagedCache(
            rows=tuple(jnp.asarray(r) for r in held["rows"]),
            block_tables=jnp.asarray(t), lengths=jnp.asarray(n),
            aux=jnp.asarray(held["aux"]))

    def host(cache):
        return ([np.asarray(r[1:]).astype(np.float32) for r in cache.rows],
                np.asarray(cache.lengths), np.asarray(cache.block_tables))

    def pools(a, b):
        """The two pools value by value. A row (one token of one
        attention, ``W`` values) is rounded value by value but perturbed
        as a whole, so a difference is sized against its row's largest
        value: how many values differ at all, the largest difference as
        a share of its row's largest value, and how many values (in how
        many rows) differ by more than 1/64 of it (two bfloat16
        roundings of a value that size)."""
        differ = beyond = rows_beyond = 0
        worst = 0.0
        for x, y in zip(a[0], b[0]):
            d = np.abs(x - y)
            differ += int(np.count_nonzero(d))
            size = np.maximum(np.abs(x).max(1), np.abs(y).max(1))[:, None]
            worst = max(worst, float((d / np.maximum(size, 1e-30)).max()))
            far = d > 2.0 ** -6 * size
            beyond += int(far.sum())
            rows_beyond += int(far.any(axis=1).sum())
        return {"values": sum(x.size for x in a[0]),
                "abs_max": max(float(np.abs(x).max()) for x in a[0]),
                "differing": differ, "largest_share_of_row_max": worst,
                "beyond_a_64th_of_row_max": beyond,
                "rows_holding_those": rows_beyond,
                "lengths_equal": bool(np.array_equal(a[1], b[1])),
                "tables_equal": bool(np.array_equal(a[2], b[2]))}

    def rows(got, want, mask):
        """Greedy tokens and logit gaps of ``got`` against ``want`` over
        the rows of ``mask``."""
        got, want = got[mask], want[mask]
        off = got.argmax(-1) != want.argmax(-1)
        top2 = np.sort(want, -1)[:, -2:]
        gap = np.abs(got - want).max(-1)
        return {"rows": int(mask.sum()), "tokens_equal": int((~off).sum()),
                "logit_gap_p50_p99_max": [
                    float(np.quantile(gap, q)) for q in (0.5, 0.99, 1.0)],
                "margins_where_tokens_differ": [
                    float(m) for m in (top2[:, 1] - top2[:, 0])[off]],
                "logit_std": float(want.std())}

    weights = fam.reference_from_serve(cfg, params)

    def reference(seqs):
        """The float32 reference's logits after each of ``seqs``."""
        ids = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
        for i, q in enumerate(seqs):
            ids[i, :len(q)] = q     # causal: padding is inert
        pos = np.asarray([[len(q) - 1] for q in seqs], np.int32)
        return np.asarray(fam.reference.logits_at(weights, ids, pos))[
            :, 0, :vocab]

    def held_to(ref, programs):
        """The cell's own statistic (``lib/serve_cell.py``
        ``warm_and_check``: the reference's top logit less its logit of
        the token served, over the top's size) for each program's
        tokens, and each program's largest logit gap to the
        reference."""
        top = ref.max(-1)
        out = {"rows": len(ref), "limit": tolerance}
        for name, lg in programs.items():
            served = np.take_along_axis(ref, lg.argmax(-1)[:, None], -1)
            gap = (top - served[:, 0]) / np.maximum(1.0, np.abs(top))
            out[name] = {"max_gap": float(gap.max()),
                         "exact": int((gap == 0).sum()),
                         "max_logit_gap": float(np.abs(lg - ref).max())}
        return out

    none = jnp.zeros((S,), bool)
    idle = jnp.zeros((S,), jnp.int32)
    for T in buckets:
        slot = int(rng.integers(0, S))
        ids, n = prompt(T)
        but = np.ones((S,), bool)
        but[slot] = False
        active, sl = jnp.asarray(but), jnp.int32(slot)
        # the two programs the rider replaces, decode then prefill
        c = state(slot, installed=False)
        want_d, c = decode(params, tokens, c, active)
        c = c.replace(block_tables=jnp.asarray(tables))
        want_p, c = prefill(params, ids, n, c, sl)
        want_d, want_p = np.asarray(want_d), np.asarray(want_p)
        apart = host(c)
        del c
        got, c = admit(params, tokens, state(slot), active, ids, n, sl)
        got = np.asarray(got)
        one = np.zeros((S,), bool)
        one[slot] = True
        # both against the float32 reference: some decode rows, the rider
        some = [s for s in rng.permutation(S) if s != slot][:args.ref_rows]
        ref = reference([fed[s] for s in some]
                        + [list(np.asarray(ids)[0, :int(n[0])])])
        say(parity={
            "bucket": T, "slot": slot, "prompt": int(n[0]), "form": "rider",
            "decode_rows": rows(got, want_d, but),
            "rider_row": rows(got, np.repeat(want_p, S, 0), one),
            "decode_rows_to_reference": held_to(ref[:-1], {
                "decode_admit": got[some], "decode": want_d[some]}),
            "rider_row_to_reference": held_to(ref[-1:], {
                "decode_admit": got[[slot]], "prefill": want_p}),
            "pool": pools(host(c), apart)})
        del c
        # nothing decoding: the program alone against the prefill
        _, c = prefill(params, ids, n, state(slot, empty=True), sl)
        apart = host(c)
        del c
        got, c = admit(params, idle, state(slot, empty=True), none, ids, n,
                       sl)
        got = np.asarray(got)
        say(parity={
            "bucket": T, "slot": slot, "prompt": int(n[0]), "form": "alone",
            "rider_row": rows(got, np.repeat(want_p, S, 0), one),
            "rider_row_to_reference": held_to(ref[-1:], {
                "decode_admit": got[[slot]], "prefill": want_p}),
            "pool": pools(host(c), apart)})
        del c, apart

    # ---- the programs' times, chained calls on the held pool
    def timed(name, fn, args_of, cache):
        out, cache = fn(*args_of(cache))
        jax.block_until_ready(out)
        t = time.perf_counter()
        for _ in range(args.calls):
            out, cache = fn(*args_of(cache))
        np.asarray(out[0, :1])
        say(program=name, calls=args.calls, prompt=live,
            ms=1e3 * (time.perf_counter() - t) / args.calls)
        return cache

    slot = 7 % S
    but = jnp.ones((S,), bool).at[slot].set(False)
    sl = jnp.int32(slot)
    live = None
    timed("decode", decode, lambda c: (params, tokens, c, everyone), state())
    for T in buckets:
        ids, n = prompt(T)
        live = int(n[0])
        timed(f"prefill_{T}", prefill, lambda c: (params, ids, n, c, sl),
              state(slot))
        timed(f"decode_admit_{T}_rider", admit,
              lambda c: (params, tokens, c, but, ids, n, sl), state(slot))
        timed(f"decode_admit_{T}_alone", admit,
              lambda c: (params, idle, c, none, ids, n, sl),
              state(slot, empty=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
