"""The held experts' small-tile grouped matmul
(``ops/pallas/grouped_matmul.py``, interpret mode on the CPU) against
``jax.lax.ragged_dot`` on seeded inputs, and the shape rule that picks it
(``held_experts.matmul_form``) with the counter that says so."""
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


# name: (rows, group sizes); the row tile is 32 at 128 rows over 4 groups
# and 16 over 8
CASES = {
    "groups-end-on-a-tile-edge": (128, [32, 32, 32, 32]),
    "groups-end-off-a-tile-edge": (128, [30, 35, 17, 46]),
    "a-group-spans-three-tiles": (128, [5, 70, 3, 50]),
    "empty-group-first": (128, [0, 50, 40, 38]),
    "empty-groups-in-the-middle": (128, [40, 0, 0, 60]),
    "empty-group-last": (128, [33, 31, 64, 0]),
    "one-group-holds-every-row": (128, [0, 0, 128, 0]),
    "no-group-holds-a-row": (128, [0, 0, 0, 0]),
    "groups-sum-below-the-rows": (128, [3, 1, 0, 9, 0, 2, 7, 1]),
    "rows-not-a-multiple-of-the-largest-tile": (176, [100, 70]),
    "rows-not-a-multiple-of-any-tile": (150, [7, 0, 60, 20, 41, 9]),
    # the decode programs' buffers, their landed picks a step (PERF.md
    # section 5), at cut widths
    "granite-decode": (640, _spread(np.random.default_rng(1), 36, 500)),
    "laguna-decode": (256, _spread(np.random.default_rng(2), 32, 90)),
    "gigachat-decode": (128, _spread(np.random.default_rng(3), 16, 19)),
    "longcat-decode": (128, _spread(np.random.default_rng(4), 16, 62)),
    "longcat-rider": (256, _spread(np.random.default_rng(5), 16, 110)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot_inside_the_groups(case, dtype):
    R, sizes = CASES[case]
    X = len(sizes)
    k1, k2 = jax.random.split(jax.random.PRNGKey(R + X))
    xs = jax.random.normal(k1, (R, K), dtype)
    w = (jax.random.normal(k2, (X, K, N), jnp.float32) / np.sqrt(K)
         ).astype(dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(gm.grouped_matmul)(xs, w, gs)
    assert got.shape == (R, N) and got.dtype == dtype
    landed = sum(sizes)
    # float32 sums of the same products: the reference in float32 from
    # the same operands, each rounded once to the output's dtype
    want = jax.lax.ragged_dot(xs.astype(jnp.float32), w.astype(jnp.float32),
                              gs).astype(dtype)
    np.testing.assert_allclose(
        np.asarray(got[:landed], np.float32),
        np.asarray(want[:landed], np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 2e-5,
        atol=2e-2 if dtype == jnp.bfloat16 else 2e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_walk_visits_each_group_tile_once_and_no_empty_group(case):
    R, sizes = CASES[case]
    X = len(sizes)
    tm = gm.row_tile(R, X, K, 2)
    group, tile, start, end, n = (np.asarray(a) for a in gm.work_items(
        jnp.asarray(sizes, jnp.int32), R, tm))
    n = int(n[0])
    assert len(group) == X + -(-R // tm) - 1 and n <= len(group)
    want = [(g, t) for g, size in enumerate(sizes) if size
            for t in range(int(start[g]) // tm, (int(end[g]) - 1) // tm + 1)]
    assert list(zip(group[:n].tolist(), tile[:n].tolist())) == want
    assert np.array_equal(end - start, sizes)
    # the items past the walk repeat its last one: no block moves
    if n:
        assert set(zip(group[n:].tolist(), tile[n:].tolist())) <= {want[-1]}


def test_tiles_follow_the_shapes():
    # the smallest tile not below the mean group; a weight block of at
    # most 4 MiB that divides the columns
    assert gm.row_tile(640, 36, 4096, 2) == 32
    assert gm.row_tile(256, 32, 2048, 2) == 16
    assert gm.row_tile(128, 16, 7168, 2) == 16
    assert gm.row_tile(25600, 36, 768, 2) == gm.MAX_ROW_TILE
    assert gm.column_tile(4096, 1536, 2) == 512
    assert gm.column_tile(768, 4096, 2) == 2048
    assert gm.column_tile(7168, 4096, 2) == 256
    assert gm.column_tile(2048, 7168, 2) == 1024
    for Kk, Nn in ((4096, 1536), (768, 4096), (2048, 1024), (512, 2048),
                   (7168, 4096), (2048, 7168), (6144, 4096), (2048, 6144)):
        tn = gm.column_tile(Kk, Nn, 2)
        assert Nn % tn == 0 and Kk * tn * 2 <= gm.WEIGHT_BLOCK_BYTES


def _traced_sites(R, X, E, Fe):
    """``_experts`` traced over abstract operands (nothing runs): its
    jaxpr as text and what the site counter read, ``{(form, rows):
    count}``."""
    reg = MetricRegistry()
    was = set_registry(reg)
    try:
        jaxpr = str(jax.make_jaxpr(
            lambda xs, gs, w_in, w_out: he._experts(
                xs, gs, {"w_in": w_in, "w_out": w_out}))(
            jax.ShapeDtypeStruct((R, E), jnp.bfloat16),
            jax.ShapeDtypeStruct((X,), jnp.int32),
            jax.ShapeDtypeStruct((X, E, 2 * Fe), jnp.bfloat16),
            jax.ShapeDtypeStruct((X, Fe, E), jnp.bfloat16)))
    finally:
        set_registry(was)
    series = reg.snapshot()["serve_moe_expert_matmul_sites_total"]["series"]
    return jaxpr, {(s["labels"]["form"], s["labels"]["rows"]): s["value"]
                   for s in series}


# (rows, held experts, E, Fe) at the published widths: the decode
# programs, LongCat's rider, the longest prefill buckets; and a test's
# toy widths, which take the same form
PROGRAMS = {
    "granite-decode": (640, 36, 4096, 768),
    "laguna-decode": (256, 32, 2048, 512),
    "gigachat-decode": (128, 16, 7168, 2048),
    "longcat-decode": (128, 16, 6144, 2048),
    "longcat-rider": (256, 16, 6144, 2048),
    "granite-prefill-4096": (25600, 36, 4096, 768),
    "laguna-prefill-8192": (8704, 32, 2048, 512),
    "toy-widths": (128, 4, 64, 32),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_programs_buffers_take_the_small_tile_kernel_and_are_counted(
        program):
    """At a cell's shapes ``_experts`` traces two calls of the Pallas
    kernel and no ``ragged_dot``, and the site counter says which form
    the shapes chose."""
    R, X, E, Fe = PROGRAMS[program]
    assert he.matmul_form(R) == "tiled"
    jaxpr, sites = _traced_sites(R, X, E, Fe)
    assert jaxpr.count(gm.NAME) == 2 and "ragged_dot" not in jaxpr
    assert sites == {("tiled", str(R)): 1.0}


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
    assert sites == {("ragged_dot", str(R)): 1.0}


def test_both_forms_give_the_layer_the_same_rows():
    """``_experts`` over the same rows in a buffer just below a row tile
    (``ragged_dot``) and padded to two (the kernel): the rows inside
    the groups agree to float32 rounding."""
    X, E, Fe = 3, 1024, 576
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    ex = {"w_in": jax.random.normal(k2, (X, E, 2 * Fe)) / 32,
          "w_out": jax.random.normal(k3, (X, Fe, E)) / 24}
    gs = jnp.asarray([3, 0, 9], jnp.int32)
    xs = jax.random.normal(k1, (15, E))
    reg = MetricRegistry()
    was = set_registry(reg)
    try:
        small = he._experts(xs, gs, ex)
        padded = he._experts(jnp.pad(xs, ((0, 17), (0, 0))), gs, ex)
    finally:
        set_registry(was)
    sites = {(s["labels"]["form"], s["labels"]["rows"]) for s in
             reg.snapshot()["serve_moe_expert_matmul_sites_total"]["series"]}
    assert sites == {("ragged_dot", "15"), ("tiled", "32")}
    np.testing.assert_allclose(np.asarray(padded[:12]),
                               np.asarray(small[:12]), rtol=1e-4, atol=1e-4)


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

    def expert(mm, up, down):
        h = jnp.square(jax.nn.relu(mm(xs, up, gs).astype(jnp.float32)))
        return h, mm(h.astype(dtype), down, gs)
    h, got = expert(gm.grouped_matmul,
                    jnp.pad(up, ((0, 0), (0, 0), (0, pad))),
                    jnp.pad(down, ((0, 0), (0, pad), (0, 0))))
    _, want = expert(jax.lax.ragged_dot, up, down)
    landed = sum(sizes)
    assert h.shape == (R, stored) and got.shape == (R, E)
    assert not np.asarray(h[:landed, F:]).any()
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got[:landed], np.float32),
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
    assert gm.row_tile(1024, 64, 2688, 2) == 16       # 12 rows a group
