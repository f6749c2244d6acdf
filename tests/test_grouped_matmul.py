"""The held experts' small-tile grouped matmul
(``ops/pallas/grouped_matmul.py``, interpret mode on the CPU) over groups
laid out on row-tile boundaries against ``jax.lax.ragged_dot`` over the
same rows packed end to end, on seeded inputs; the layout
``held_experts._align`` builds for it, through ``held_experts_part``
against a dense per-pick reference; and the shape rule that picks the
kernel (``held_experts.matmul_form``) with the counter that says so."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.model_implementations import held_experts as he
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.telemetry.registry import MetricRegistry, set_registry

K, N = 256, 384


def _spread(rng, X, landed):
    return rng.multinomial(landed, rng.dirichlet(np.full(X, 4.0))).tolist()


# name: (rows packed end to end, group sizes); the row tile is 64 at 128
# rows over 4 groups (the smallest power of two ABOVE the mean group), 32
# over 8, 128 at 176 over 2
CASES = {
    "groups-end-on-a-tile-edge": (128, [32, 32, 32, 32]),
    "groups-end-off-a-tile-edge": (128, [30, 35, 17, 46]),
    "a-group-spans-three-tiles": (192, [5, 70, 3, 2, 50, 0, 1, 9]),
    "a-group-of-exactly-one-tile": (128, [64, 7, 32, 1]),
    "a-group-of-one-tile-and-a-row": (128, [65, 7, 33, 1]),
    "empty-group-first": (128, [0, 50, 40, 38]),
    "empty-groups-in-the-middle": (128, [40, 0, 0, 60]),
    "empty-group-last": (128, [33, 31, 64, 0]),
    "the-last-groups-are-empty": (128, [9, 17, 0, 16, 0, 0, 0, 0]),
    "one-group-holds-every-row": (128, [0, 0, 128, 0]),
    "no-group-holds-a-row": (128, [0, 0, 0, 0]),
    "groups-sum-below-the-rows": (128, [3, 1, 0, 9, 0, 2, 7, 1]),
    "rows-not-a-multiple-of-the-largest-tile": (176, [100, 70]),
    "rows-not-a-multiple-of-any-tile": (150, [7, 0, 60, 20, 41, 9]),
    # the decode programs' buffers, their landed picks a step (PERF.md
    # section 5), at cut widths
    "granite-decode": (640, _spread(np.random.default_rng(1), 36, 500)),
    "nemotron-decode": (1024, _spread(np.random.default_rng(6), 64, 768)),
    "laguna-decode": (256, _spread(np.random.default_rng(2), 32, 90)),
    "gigachat-decode": (128, _spread(np.random.default_rng(3), 16, 19)),
    "longcat-decode": (128, _spread(np.random.default_rng(4), 16, 62)),
    "longcat-rider": (256, _spread(np.random.default_rng(5), 16, 110)),
}


def _laid_out(packed, sizes, tm):
    """``packed [R, K]`` (the groups end to end) on boundaries of ``tm``,
    by the host: the buffer ``[R', K]`` (a finite filler in the rows that
    belong to no group) and each packed row's place in it."""
    R, X = packed.shape[0], len(sizes)
    astart, _ = (np.asarray(a) for a in gm.aligned_starts(
        jnp.asarray(sizes, jnp.int32), tm))
    at = np.concatenate([astart[g] + np.arange(n) for g, n in enumerate(sizes)]
                        + [np.zeros(0, np.int64)]).astype(np.int64)
    assert all(a % tm == 0 for a in astart) and at.size == sum(sizes)
    buf = np.full((gm.aligned_rows(R, X, tm), packed.shape[1]), 0.5,
                  np.float32)
    assert at.size == 0 or at.max() < buf.shape[0]
    buf[at] = np.asarray(packed[:at.size], np.float32)
    return jnp.asarray(buf, packed.dtype), at


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot_inside_the_groups(case, dtype):
    R, sizes = CASES[case]
    X = len(sizes)
    tm = gm.row_tile(R, X, K, 2)
    k1, k2 = jax.random.split(jax.random.PRNGKey(R + X))
    packed = jax.random.normal(k1, (R, K), dtype)
    w = (jax.random.normal(k2, (X, K, N), jnp.float32) / np.sqrt(K)
         ).astype(dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    xs, at = _laid_out(packed, sizes, tm)
    got = jax.jit(gm.grouped_matmul, static_argnames="tm")(xs, w, gs, tm=tm)
    assert got.shape == (xs.shape[0], N) and got.dtype == dtype
    landed = sum(sizes)
    # float32 sums of the same products: the reference in float32 from
    # the same operands, each rounded once to the output's dtype
    want = jax.lax.ragged_dot(packed.astype(jnp.float32),
                              w.astype(jnp.float32), gs).astype(dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[at],
        np.asarray(want[:landed], np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 2e-5,
        atol=2e-2 if dtype == jnp.bfloat16 else 2e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_walk_gives_every_tile_one_group_and_an_empty_group_none(case):
    """Every tile has one group, every group ``ceil(n / tm)`` tiles in
    order, an empty group none; the items past the walk repeat its last
    one, so that no block moves."""
    R, sizes = CASES[case]
    X = len(sizes)
    tm = gm.row_tile(R, X, K, 2)
    tiles = gm.aligned_rows(R, X, tm) // tm
    group, n = (np.asarray(a) for a in gm.work_items(
        jnp.asarray(sizes, jnp.int32), tiles, tm))
    n = int(n[0])
    want = [g for g, size in enumerate(sizes) for _ in range(-(-size // tm))]
    assert len(group) == tiles and n == len(want) <= tiles
    assert group[:n].tolist() == want
    astart, aend = (np.asarray(a) for a in gm.aligned_starts(
        jnp.asarray(sizes, jnp.int32), tm))
    assert all(astart[g] <= i * tm < aend[g] for i, g in enumerate(want))
    if n:
        assert set(group[n:].tolist()) <= {want[-1]}


def test_the_aligned_buffer_holds_the_worst_case_and_no_more():
    """``aligned_rows`` is what ``R`` rows need however they fall into
    ``X`` groups: every group one row over whole tiles fills it."""
    for R, X, tm in ((1024, 64, 16), (640, 36, 32), (128, 16, 16),
                     (25600, 36, 128), (20, 64, 16), (16, 1, 16)):
        rows = gm.aligned_rows(R, X, tm)
        assert rows % tm == 0 and rows >= R
        hit = min(X, R)                 # one row over, as many as can be
        worst = np.full(hit, 1)
        spare = R - hit
        worst[0] += spare // tm * tm
        padded = int((-(-worst // tm) * tm).sum())
        assert padded <= rows < padded + tm + (spare % tm > 0) * tm


def test_tiles_follow_the_shapes():
    # the smallest tile ABOVE the mean group (a tile at the mean is
    # outgrown by the busy half of the groups); a weight block of at most
    # 4 MiB that divides the columns
    assert gm.row_tile(640, 36, 4096, 2) == 32        # 17.8 rows a group
    assert gm.row_tile(1024, 64, 2688, 2) == 32       # 16
    assert gm.row_tile(1023, 64, 2688, 2) == 16       # just under 16
    assert gm.row_tile(256, 32, 2048, 2) == 16
    assert gm.row_tile(128, 16, 7168, 2) == 16
    assert gm.row_tile(25600, 36, 768, 2) == gm.MAX_ROW_TILE
    assert gm.row_tile(8192, 64, 768, 2) == gm.MAX_ROW_TILE     # 128
    assert gm.row_tile(3072, 16, 6144, 2) == 64       # a row block of 1 MiB
    assert gm.column_tile(4096, 1536, 2) == 512
    assert gm.column_tile(768, 4096, 2) == 2048
    assert gm.column_tile(7168, 4096, 2) == 256
    assert gm.column_tile(2048, 7168, 2) == 1024
    for Kk, Nn in ((4096, 1536), (768, 4096), (2048, 1024), (512, 2048),
                   (7168, 4096), (2048, 7168), (6144, 4096), (2048, 6144)):
        tn = gm.column_tile(Kk, Nn, 2)
        assert Nn % tn == 0 and Kk * tn * 2 <= gm.WEIGHT_BLOCK_BYTES


def test_an_expert_has_one_row_tile_for_both_of_its_matmuls():
    """The smaller of what each matmul's shapes give: LongCat's 3072-row
    fallback gets 64 under ``w_in``'s K of 6144 and 128 under ``w_out``'s
    2048; a buffer under one row tile (``ragged_dot``) is packed end to
    end, which is a tile of 1."""
    assert gm.row_tile(3072, 16, 6144, 2) == 64
    assert gm.row_tile(3072, 16, 2048, 2) == 128
    assert he.expert_row_tile(3072, 16, 6144, 2048, 2) == 64
    assert he.expert_row_tile(640, 36, 4096, 768, 2) == 32
    assert he.expert_row_tile(15, 16, 4096, 768, 2) == 1


def _traced_sites(R, X, E, Fe):
    """``_experts`` traced over abstract operands (nothing runs) at the
    buffer and the row tile ``R`` landed picks are given: its jaxpr as
    text and what the site counter read, ``{(form, rows, row_tile):
    count}``."""
    tm = he.expert_row_tile(R, X, E, Fe, 2)
    reg = MetricRegistry()
    was = set_registry(reg)
    try:
        jaxpr = str(jax.make_jaxpr(
            lambda xs, gs, w_in, w_out: he._experts(
                xs, gs, {"w_in": w_in, "w_out": w_out}, tm=tm))(
            jax.ShapeDtypeStruct((gm.aligned_rows(R, X, tm), E),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct((X,), jnp.int32),
            jax.ShapeDtypeStruct((X, E, 2 * Fe), jnp.bfloat16),
            jax.ShapeDtypeStruct((X, Fe, E), jnp.bfloat16)))
    finally:
        set_registry(was)
    series = reg.snapshot()["serve_moe_expert_matmul_sites_total"]["series"]
    return jaxpr, {(s["labels"]["form"], s["labels"]["rows"],
                    s["labels"]["row_tile"]): s["value"] for s in series}


# (landed picks the buffer is for, held experts, E, Fe, the buffer's rows,
# its row tile) at the published widths: the decode programs, LongCat's
# rider, the longest prefill buckets; and a test's toy widths, which take
# the same form
PROGRAMS = {
    "granite-decode": (640, 36, 4096, 768, 1728, 32),
    "laguna-decode": (256, 32, 2048, 512, 736, 16),
    "gigachat-decode": (128, 16, 7168, 2048, 368, 16),
    "longcat-decode": (128, 16, 6144, 2048, 368, 16),
    "longcat-rider": (256, 16, 6144, 2048, 736, 32),
    "granite-prefill-4096": (25600, 36, 4096, 768, 30080, 128),
    "laguna-prefill-8192": (8704, 32, 2048, 512, 12672, 128),
    "toy-widths": (128, 4, 64, 32, 320, 64),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_programs_buffers_take_the_small_tile_kernel_and_are_counted(
        program):
    """At a cell's shapes ``_experts`` traces two calls of the Pallas
    kernel and no ``ragged_dot``, and the site counter says which form
    the shapes chose."""
    R, X, E, Fe, rows, tm = PROGRAMS[program]
    assert he.matmul_form(R) == "tiled"
    jaxpr, sites = _traced_sites(R, X, E, Fe)
    assert jaxpr.count(gm.NAME) == 2 and "ragged_dot" not in jaxpr
    assert sites == {("tiled", str(rows), str(tm)): 1.0}


# what stays ``ragged_dot``: a buffer below one row tile, whatever the
# experts
KEPT = {
    "four-rows": (4, 16, 4096, 768),
    "fifteen-rows": (15, 16, 4096, 768),
}


@pytest.mark.parametrize("program", sorted(KEPT))
def test_a_buffer_under_one_row_tile_keeps_ragged_dot(program):
    R, X, E, Fe = KEPT[program]
    assert R < gm.MIN_ROW_TILE and he.matmul_form(R) == "ragged_dot"
    jaxpr, sites = _traced_sites(R, X, E, Fe)
    assert gm.NAME not in jaxpr and jaxpr.count("ragged_dot_general[") == 2
    assert sites == {("ragged_dot", str(R), "1"): 1.0}


def test_both_forms_give_the_layer_the_same_rows():
    """``_experts`` over the same rows packed end to end in a buffer just
    below a row tile (``ragged_dot``) and laid out on tiles of 16 (the
    kernel): the rows inside the groups agree to float32 rounding."""
    X, E, Fe = 3, 1024, 576
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    ex = {"w_in": jax.random.normal(k2, (X, E, 2 * Fe)) / 32,
          "w_out": jax.random.normal(k3, (X, Fe, E)) / 24}
    gs = jnp.asarray([3, 0, 9], jnp.int32)
    xs = jax.random.normal(k1, (15, E))
    reg = MetricRegistry()
    was = set_registry(reg)
    try:
        small = he._experts(xs, gs, ex, tm=1)
        laid, at = _laid_out(xs, [3, 0, 9], 16)
        tiled = he._experts(laid, gs, ex, tm=16)
    finally:
        set_registry(was)
    sites = {(s["labels"]["form"], s["labels"]["rows"],
              s["labels"]["row_tile"]) for s in
             reg.snapshot()["serve_moe_expert_matmul_sites_total"]["series"]}
    assert sites == {("ragged_dot", "15", "1"), ("tiled", "48", "16")}
    np.testing.assert_allclose(np.asarray(tiled)[at],
                               np.asarray(small[:12]), rtol=1e-4, atol=1e-4)


# ---------------------------------- the layout, through held_experts_part

def _routed(T, k, experts, held, E, Fe, seed, act="swiglu"):
    """A seeded expert layer's operands: tokens, top-k picks over
    ``experts`` router outputs with their weights, the last three rows
    padding, and the held range's weights."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    X = held[1] - held[0]
    u = jax.random.normal(ks[0], (T, E))
    wide = Fe if act == "relu2" else 2 * Fe
    ex = {"w_in": jax.random.normal(ks[1], (X, E, wide)) / np.sqrt(E),
          "w_out": jax.random.normal(ks[2], (X, Fe, E)) / np.sqrt(Fe)}
    weights, picks = jax.lax.top_k(
        jax.nn.softmax(jax.random.normal(ks[3], (T, experts))), k)
    return u, picks, weights, jnp.arange(T) < T - 3, ex


def _dense_per_pick(u, picks, weights, held, lo, ex, act):
    """Every landed pick on its own: the token's row through its
    expert's two matrices in float64, weighted and summed a token."""
    u, picks, weights, held = (np.asarray(a, np.float64)
                               for a in (u, picks, weights, held))
    w_in, w_out = (np.asarray(ex[n], np.float64) for n in ("w_in", "w_out"))
    Fe = w_out.shape[1]
    want = np.zeros(u.shape)
    for t, i in zip(*np.nonzero(held)):
        x = int(picks[t, i]) - lo
        a = u[t] @ w_in[x]
        h = (np.square(np.maximum(a, 0.0)) if act == "relu2"
             else a[:Fe] / (1.0 + np.exp(-a[:Fe])) * a[Fe:])
        want[t] += weights[t, i] * (h @ w_out[x])
    return want


# name: (T, k, router outputs, held, E, Fe, fast, act): a decode batch
# whose buffer is laid out on tiles of 32 (12 picks a held expert), a
# prompt on tiles of 128 that combines by gather, and a buffer that takes
# the exact ``T k`` fallback
LAYERS = {
    "decode-batch": (64, 6, 32, (8, 24), 128, 128, 256, "swiglu"),
    "decode-batch-ungated": (64, 6, 32, (8, 24), 128, 128, 256, "relu2"),
    "prompt-combined-by-gather": (640, 4, 16, (0, 8), 128, 128, 1536,
                                  "swiglu"),
    "more-landed-than-the-buffer": (64, 6, 32, (8, 24), 128, 128, 128,
                                    "swiglu"),
}


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_held_experts_part_is_the_dense_per_pick_sum(layer):
    """``held_experts_part`` over the aligned buffer equals (float32,
    1e-6 of the largest entry) the exact ``T k`` path and a dense
    per-pick reference, and says how many row tiles it walked."""
    T, k, experts, held_range, E, Fe, fast, act = LAYERS[layer]
    u, picks, weights, valid, ex = _routed(T, k, experts, held_range, E, Fe,
                                           seed=len(layer), act=act)
    order, where, held, gs = he.sort_picks(picks, valid, held_range)

    def part(fast):
        return jax.jit(lambda *a: he.held_experts_part(
            *a, ex, fast=fast, act=act))(u, order, where, held, weights, gs)
    with jax.default_matmul_precision("highest"):
        got, walked = part(fast)
        exact, _ = part(T * k)
    want = _dense_per_pick(u, picks, weights, held, held_range[0], ex, act)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - np.asarray(exact)).max() <= 1e-6 * scale
    assert np.abs(np.asarray(got) - want).max() <= 1e-6 * scale
    landed = int(gs.sum())
    rows = fast if landed <= fast else T * k
    tm = he.expert_row_tile(rows, len(gs), E, Fe, 4)
    assert int(walked) == sum(-(-int(n) // tm) for n in gs)
    form = he.combine_form(T, k, gm.aligned_rows(rows, len(gs), tm),
                           exact=landed > fast)
    assert (form == "gathered") == (layer == "prompt-combined-by-gather")
    assert (landed <= fast) == (layer != "more-landed-than-the-buffer")


@pytest.mark.parametrize("over", [0, 1], ids=["at-fast", "one-over"])
def test_the_fast_buffer_serves_exactly_the_steps_whose_picks_fit(
        over, monkeypatch):
    """The predicate is the parent's, ``sum(group_sizes) <= fast``, on
    the picks and not on the aligned rows: ``fast`` landed picks take the
    fast buffer, one more the exact fallback, however they fall into
    groups (here every group one row over whole tiles of 32: the worst
    case, which fills the aligned buffer to its last tile)."""
    T, k, X, E, Fe, fast = 96, 4, 8, 128, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(over), 3)
    u = jax.random.normal(ks[0], (T, E))
    ex = {"w_in": jax.random.normal(ks[1], (X, E, 2 * Fe)) / np.sqrt(E),
          "w_out": jax.random.normal(ks[2], (X, Fe, E)) / np.sqrt(Fe)}
    sizes = [97, 1, 1, 1, 1, 1, 1, 25 + over]
    assert sum(sizes) == fast + over
    flat = np.full(T * k, X + 1)                     # absent
    flat[:sum(sizes)] = np.repeat(np.arange(X), sizes)
    picks = jnp.asarray(np.random.default_rng(0).permutation(flat)
                        .reshape(T, k))
    weights = jnp.full((T, k), 0.25)
    order, where, held, gs = he.sort_picks(picks, jnp.ones(T, bool), (0, X))
    assert gs.tolist() == sizes
    seen = []
    experts = he._experts

    def watched(xs, *a, **kw):
        jax.debug.callback(lambda: seen.append(xs.shape[0]))
        return experts(xs, *a, **kw)
    monkeypatch.setattr(he, "_experts", watched)
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(lambda *a: he.held_experts_part(*a, ex, fast=fast))(
            u, order, where, held, weights, gs)
        jax.effects_barrier()
    tm = he.expert_row_tile(fast, X, E, Fe, 4)
    assert tm == 32 and (over or sum(-(-n // tm) * tm for n in sizes)
                         == gm.aligned_rows(fast, X, tm))
    assert seen == [gm.aligned_rows(T * k if over else fast, X, tm if not
                                    over else he.expert_row_tile(
                                        T * k, X, E, Fe, 4))]
    want = _dense_per_pick(u, picks, weights, held, 0, ex, "swiglu")
    assert np.abs(np.asarray(got) - want).max() <= 1e-6 * np.abs(want).max()


# name: (T, k, rows, the exact fallback) -> the form of the weighted sum
COMBINE_FORMS = {
    "nemotron-decode": ((256, 6, 3008, False), "gathered"),
    "granite-decode": ((96, 10, 1728, False), "landed"),
    "longcat-decode": ((256, 12, 368, False), "landed"),
    "gigachat-chunk": ((1024, 8, 1600, False), "gathered"),
    "longcat-rider": ((512, 12, 736, False), "landed"),
    "laguna-prefill-8192": ((8192, 8, 12672, False), "gathered"),
    "longcat-rider-fallback": ((512, 12, 7152, True), "landed"),
    "longcat-rider-fallback-if-it-ran": ((512, 12, 7152, False), "gathered"),
    "gigachat-chunk-fallback": ((1024, 8, 10224, True), "gathered"),
}


@pytest.mark.parametrize("site", sorted(COMBINE_FORMS))
def test_the_combine_is_chosen_by_rows_a_pick_and_the_fallback_by_bytes(
        site):
    """The product where its ``[T, rows]`` fits and the padded buffer is
    under ``ASSIGN_ROWS_A_PICK`` rows a pick; the exact fallback keeps the
    product wherever it fits (it holds no ``[T, k, E]`` for speed)."""
    args, want = COMBINE_FORMS[site]
    assert he.combine_form(*args) == want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("tm", [1, 16, 32])
def test_both_dispatch_forms_lay_out_the_same_rows(tm, dtype, monkeypatch):
    """The one-hot product (a decode batch) and the gather (a prompt) put
    the same token's row, to the bit, at every row that is a pick, mark
    the same rows as picks and give every pick the same row; what lies
    between is finite."""
    T, k, X, E = 48, 4, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(tm), 2)
    u = jax.random.normal(ks[0], (T, E), dtype)
    picks = jax.random.randint(ks[1], (T, k), 0, 2 * X)
    valid = jnp.arange(T) < T - 3
    order, where, held, gs = he.sort_picks(picks, valid, (0, X))
    rows = gm.aligned_rows(T * k, X, tm)
    got = {}
    for form, cells in (("one-hot", T * E), ("gather", T * E - 1)):
        monkeypatch.setattr(he, "ONE_HOT_CELLS", cells)
        got[form] = jax.jit(lambda *a: he._align(*a, k, rows, tm))(
            u, order, where, held, gs)
    (xs, landed, at), (xs_g, landed_g, at_g) = got["one-hot"], got["gather"]
    landed = np.asarray(landed)
    assert landed.sum() == int(gs.sum())
    assert (landed == np.asarray(landed_g)).all()
    assert (np.asarray(at) == np.asarray(at_g)).all()
    assert (np.asarray(xs)[landed] == np.asarray(xs_g)[landed]).all()
    assert np.isfinite(np.asarray(xs, np.float32)).all()
    assert np.isfinite(np.asarray(xs_g, np.float32)).all()
    # every held pick's row holds its token
    t, j = np.nonzero(np.asarray(held))
    assert (np.asarray(xs)[np.asarray(at)[t, j]] == np.asarray(u)[t]).all()


def test_three_bfloat16_parts_carry_every_bit_of_the_weights():
    """Against bfloat16 rows ``_combine_landed`` multiplies the weights'
    three bfloat16 parts, one pass each: the float32 product of the same
    operands to its last bits, and rows that are no pick (here infinite)
    never reach a token."""
    T, k, rows, E = 24, 4, 160, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    out = jax.random.normal(ks[0], (rows, E), jnp.bfloat16)
    where = jax.random.permutation(ks[1], rows)[:T * k].reshape(T, k)
    held = jax.random.uniform(ks[2], (T, k)) < 0.7
    landed = jnp.zeros(rows, bool).at[jnp.where(held, where, rows)].set(
        True, mode="drop")
    out = jnp.where(landed[:, None], out, jnp.inf)
    w = jax.random.uniform(ks[2], (T, k), jnp.float32)
    got = np.asarray(jax.jit(he._combine_landed)(out, landed, where, held, w))
    want = np.zeros((T, E))
    for t, j in zip(*np.nonzero(np.asarray(held))):
        want[t] += float(w[t, j]) * np.asarray(out[where[t, j]], np.float64)
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
    gathered = np.asarray(he._combine_gathered(out, where, held, w))
    assert np.abs(gathered - want).max() <= 1e-6 * np.abs(want).max()


def test_the_walked_tiles_ride_the_routing_row_to_their_own_series():
    """``routing_counts`` ends with what ``held_experts_part`` walked, and
    ``counter_series`` names ``serve_moe_row_tiles_walked_total`` behind
    that column, a program."""
    picks = jnp.asarray([[0, 5], [1, 9], [1, 2]])
    valid = jnp.asarray([True, True, False])
    _, _, held, gs = he.sort_picks(picks, valid, (0, 4))
    row = he.routing_counts(picks, held, gs, valid, 8, jnp.int32(7))
    assert row.shape == (4 + len(he.COUNTER_TAIL),)
    assert dict(zip(he.COUNTER_TAIL, row[4:].tolist())) == {
        "identity_picks": 1, "absent_picks": 1, "tokens_routed": 2,
        "layer_calls": 1, "held_experts_hit": 2, "row_tiles_walked": 7}
    reg = MetricRegistry()
    series = he.counter_series(reg, 4, ("decode", "prefill"))
    assert [len(s) for s in series] == [row.shape[0]] * 2
    series[1][-1].inc(7)
    walked = reg.snapshot()["serve_moe_row_tiles_walked_total"]["series"]
    assert {s["labels"]["program"]: s["value"] for s in walked} == {
        "decode": 0.0, "prefill": 7.0}


# ------------------------- widths that are no multiple of the 128 lanes

# name: (E, the expert's width, the width as stored): an up projection
# ``[E, stored]`` whose columns past the width are zeros and the down
# projection ``[stored, E]`` that contracts over them, and a toy width
# stored as it is (one block)
STORED_WIDTHS = {
    "half-a-lane-over": (192, 464, 512),
    "one-column-over": (256, 257, 384),
    "toy-width-one-block": (200, 72, 72),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(STORED_WIDTHS))
def test_an_expert_stored_padded_to_whole_lanes_is_the_expert(case, dtype):
    """An ungated expert whose width is no multiple of 128, stored padded
    with zeros to whole lanes, through the kernel (interpret mode)
    against ``ragged_dot`` over the weights at their own width: the
    padding's columns come out zero, ``relu(0)^2`` keeps them zero and
    the down projection's zero rows add nothing."""
    E, F, stored = STORED_WIDTHS[case]
    sizes = [30, 0, 35, 17, 9, 0, 20, 5]
    R, X = 128, len(sizes)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(E + F), 3)
    xs = jax.random.normal(k1, (R, E), dtype)
    up = (jax.random.normal(k2, (X, E, F), jnp.float32) / np.sqrt(E)
          ).astype(dtype)
    down = (jax.random.normal(k3, (X, F, E), jnp.float32) / np.sqrt(F)
            ).astype(dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    pad = stored - F

    def expert(mm, xs, up, down):
        h = jnp.square(jax.nn.relu(mm(xs, up, gs).astype(jnp.float32)))
        return h, mm(h.astype(dtype), down, gs)
    tm = gm.row_tile(R, X, E, 2)
    laid, at = _laid_out(xs, sizes, tm)
    h, got = expert(lambda *a: gm.grouped_matmul(*a, tm=tm), laid,
                    jnp.pad(up, ((0, 0), (0, 0), (0, pad))),
                    jnp.pad(down, ((0, 0), (0, pad), (0, 0))))
    _, want = expert(jax.lax.ragged_dot, xs, up, down)
    landed = sum(sizes)
    assert h.shape == (laid.shape[0], stored)
    assert got.shape == (laid.shape[0], E)
    assert not np.asarray(h)[at][:, F:].any()
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32)[at],
                               np.asarray(want[:landed], np.float32),
                               rtol=tol, atol=tol)


def test_a_width_that_is_no_multiple_of_the_lanes_is_one_block_or_refused():
    """``[2688, 1856]`` (an up projection 14.5 x 128 wide) would be one
    9.98 MB block, two in flight 20 MB against 16 MiB of scoped VMEM: it
    is refused, and the message says how to store it; stored 1920 wide it
    tiles in whole lanes inside the 4 MiB budget, as the down projection
    that contracts over 1920 does. A toy width stays one block."""
    with pytest.raises(ValueError, match="padded to whole lanes"):
        gm.column_tile(2688, 1856, 2)
    assert gm.column_tile(2688, 1920, 2) == 640
    assert gm.column_tile(1920, 2688, 2) == 896
    for Kk, Nn in ((2688, 1920), (1920, 2688)):
        tn = gm.column_tile(Kk, Nn, 2)
        assert tn % gm.LANES == 0 and Kk * tn * 2 <= gm.WEIGHT_BLOCK_BYTES
        assert Nn // tn == 3
    assert gm.column_tile(32, 48, 4) == 48
    assert gm.row_tile(1024, 64, 2688, 2) == 32       # 12 of 16 rows a group
