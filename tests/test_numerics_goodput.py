"""Training numerics observatory + goodput accounting (ISSUE 4).

The acceptance run is here: a two-block toy model with NaN injected into
one block's gradients gets the provenance event naming that block (event
ring + ``/debug/numerics`` over HTTP); with numerics off the step
program is byte-identical (one executable, unchanged metrics keys) and
toggling costs exactly one retrace the compile watch attributes by the
static flag; the fp16 overflow-skip path leaves params byte-identical
while counting ``train_overflow_skips_total``; and goodput buckets sum
to the step wall time exactly.
"""
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import (EventRing, MetricRegistry,
                                     NumericsWatch, block_nonfinite_counts,
                                     block_spec, block_sq_norms,
                                     get_event_ring, get_registry,
                                     numerics_snapshot, set_event_ring,
                                     set_registry)


@pytest.fixture()
def fresh_telemetry():
    """Private process registry + event ring for the duration of one
    test — engines built inside see only their own metrics/events."""
    prev_reg = set_registry(MetricRegistry())
    prev_ring = set_event_ring(EventRing(256))
    try:
        yield get_registry()
    finally:
        set_registry(prev_reg)
        set_event_ring(prev_ring)


def _make_engine(telemetry=None, fp16=False, gas=1, lr=0.01):
    """Two-block toy model; ``batch["gscale"]`` injects into blk1's
    gradients only (grad wrt blk1.w includes mean(gscale); blk0's grads
    come from the mse term alone)."""
    params = {"blk0": {"w": jnp.full((16, 8), 0.1, jnp.float32)},
              "blk1": {"w": jnp.full((8, 4), 0.1, jnp.float32)}}

    def loss_fn(p, b, rng):
        h = jnp.tanh(b["x"] @ p["blk0"]["w"])
        y = h @ p["blk1"]["w"]
        return (jnp.mean((y - b["y"]) ** 2)
                + jnp.mean(b["gscale"]) * jnp.sum(p["blk1"]["w"]))

    cfg = {"train_micro_batch_size_per_gpu": 4, "steps_per_print": 1,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "sgd", "params": {"lr": lr}}}
    if fp16:
        cfg["fp16"] = {"enabled": True}
    if telemetry is not None:
        cfg["telemetry"] = telemetry
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=loss_fn, model_parameters=params, config=cfg)
    return engine


def _batch(engine, y_offset=0.0, gscale=0.0, seed=0):
    rng = np.random.default_rng(seed)
    B = engine.train_batch_size
    return {"x": jnp.asarray(rng.normal(size=(B, 16)), jnp.float32),
            "y": jnp.full((B, 4), y_offset, jnp.float32),
            "gscale": jnp.full((B,), gscale, jnp.float32)}


# ---------------------------------------------------------------------------
# block grouping + in-graph helpers
# ---------------------------------------------------------------------------

def test_block_spec_grouping_by_depth():
    tree = {"a": {"x": jnp.ones(2), "y": jnp.ones(3)},
            "b": {"x": jnp.ones(4)}}
    s1 = block_spec(tree, depth=1)
    assert s1.names == ("a", "b")
    assert s1.leaf_block == (0, 0, 1)
    s2 = block_spec(tree, depth=2)
    assert s2.names == ("a/x", "a/y", "b/x")
    # depth beyond the path length groups under the full path
    s9 = block_spec(tree, depth=9)
    assert len(s9) == 3
    with pytest.raises(ValueError):
        block_spec(tree, depth=0)


def test_block_norms_and_nonfinite_in_graph():
    tree = {"a": jnp.asarray([3.0, 4.0]),
            "b": jnp.asarray([jnp.inf, 1.0, jnp.nan])}
    spec = block_spec(tree, depth=1)

    @jax.jit
    def stats(t):
        return block_sq_norms(t, spec), block_nonfinite_counts(t, spec)

    sq, nf = stats(tree)
    assert np.allclose(np.asarray(sq)[0], 25.0)   # 3² + 4²
    assert list(np.asarray(nf)) == [0, 2]
    # structure mismatch is loud, not silently misattributed
    with pytest.raises(ValueError):
        block_sq_norms({"a": jnp.ones(2)}, spec)


def test_spike_detector_median_mad(fresh_telemetry):
    reg = fresh_telemetry
    w = NumericsWatch(["b0"], registry=reg, window=8, threshold=6.0)
    for i in range(10):
        assert w.observe(step=i, loss=1.0 + 0.01 * (i % 3)) is None
    assert w.observe(step=10, loss=50.0) == "loss_spike"
    assert w.anomalies_total == 1
    snap = reg.snapshot()
    assert snap["train_numerics_anomaly"]["series"][0]["value"] == 1.0
    assert snap["train_numerics_anomalies_total"]["series"][0]["value"] == 1
    # non-finite loss is an anomaly even with spike detection disabled
    w2 = NumericsWatch(["b0"], registry=reg, window=8, threshold=None)
    assert w2.observe(step=0, loss=float("nan")) == "nonfinite_loss"
    # the snapshot's active flag mirrors the gauge: ONE clean step does
    # not clear it — only a full clean window re-arms both
    w.observe(step=11, loss=1.0)
    assert w.snapshot()["anomaly"]["active"] == 1
    assert reg.snapshot()["train_numerics_anomaly"]["series"][0][
        "value"] == 1.0
    for i in range(12, 12 + w.window):
        w.observe(step=i, loss=1.0)
    assert w.snapshot()["anomaly"]["active"] == 0
    assert reg.snapshot()["train_numerics_anomaly"]["series"][0][
        "value"] == 0.0


# ---------------------------------------------------------------------------
# engine integration: off = zero extra traces; toggle = one retrace
# ---------------------------------------------------------------------------

def test_numerics_off_zero_extra_traces_and_toggle(fresh_telemetry):
    engine = _make_engine()
    try:
        m = engine.train_batch(_batch(engine))
        engine.train_batch(_batch(engine))
        assert sorted(m.keys()) == ["grad_norm", "loss", "loss_scale",
                                    "lr", "skipped"]
        assert engine._step_fn._cache_size() == 1      # no retrace
        # the static flag must not break the AOT fast path: the watched
        # executable ran both steps (Compiled.__call__ takes the dynamic
        # arguments only; a static passed through raises)
        rec = engine._step_fn.executables[0]
        assert rec.compiled is not None
        assert rec.calls == 2
        assert "train_block_grad_norm" not in engine.telemetry.snapshot()
        # toggle on: exactly one retrace, attributed to the static flag
        engine.set_numerics_enabled(True)
        m = engine.train_batch(_batch(engine))
        assert "_numerics" not in m                    # popped by engine
        assert engine._step_fn._cache_size() == 2
        assert len(engine._step_fn.retraces) == 1
        assert engine._step_fn.retraces[0]["changed"] == [
            "numerics_on: static:False -> static:True"]
        # toggling back reuses the cached executable — no third compile
        engine.set_numerics_enabled(False)
        engine.train_batch(_batch(engine))
        assert engine._step_fn._cache_size() == 2
        snap = engine.telemetry.snapshot()
        blocks = {s["labels"]["block"]: s["value"]
                  for s in snap["train_block_grad_norm"]["series"]}
        assert set(blocks) == {"blk0", "blk1"}
        ratios = {s["labels"]["block"]: s["value"]
                  for s in snap["train_block_update_ratio"]["series"]}
        assert all(r > 0 for r in ratios.values())     # sgd: lr*grad
    finally:
        engine.destroy()


def test_nonfinite_provenance_names_block_and_debug_route(fresh_telemetry):
    engine = _make_engine(telemetry={"numerics_enabled": True,
                                     "http_port": 0}, gas=2)
    try:
        engine.train_batch(_batch(engine))
        engine.train_batch(_batch(engine, gscale=float("nan")))
        snap = engine.numerics.snapshot()
        assert snap["nonfinite"]["steps_total"] == 1
        assert snap["nonfinite"]["last"]["block"] == "blk1"
        assert "blk0" not in snap["nonfinite"]["last"]["blocks"]
        evs = [e for e in get_event_ring().snapshot()
               if e["kind"] == "numerics_nonfinite"]
        assert len(evs) == 1
        assert evs[0]["data"]["first_block"] == "blk1"
        assert evs[0]["data"]["source"] == "train"
        reg_snap = engine.telemetry.snapshot()
        assert reg_snap["train_nonfinite_steps_total"]["series"][0][
            "value"] == 1
        assert reg_snap["train_numerics_anomaly"]["series"][0][
            "value"] == 1.0
        # the same provenance over HTTP
        port = engine._telemetry_http.port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/numerics", timeout=10).read()
        remote = json.loads(body)
        assert remote["train"]["nonfinite"]["last"]["block"] == "blk1"
        assert remote["train"]["blocks"] == ["blk0", "blk1"]
    finally:
        engine.destroy()
    # destroy() unregisters the watch from the process surface
    assert "train" not in numerics_snapshot()


def test_fp16_skip_leaves_params_identical_counts_overflow(fresh_telemetry):
    engine = _make_engine(telemetry={"numerics_enabled": True}, fp16=True)
    try:
        engine.train_batch(_batch(engine))
        before = {k: np.asarray(v).tobytes()
                  for k, v in [("b0", engine.state.params["blk0"]["w"]),
                               ("b1", engine.state.params["blk1"]["w"])]}
        m = engine.train_batch(_batch(engine, gscale=float("nan")))
        assert bool(m["skipped"]) is True
        after = {k: np.asarray(v).tobytes()
                 for k, v in [("b0", engine.state.params["blk0"]["w"]),
                              ("b1", engine.state.params["blk1"]["w"])]}
        assert before == after                 # skip = byte-identical
        assert engine.skipped_steps == 1
        snap = engine.telemetry.snapshot()
        assert snap["train_overflow_skips_total"]["series"][0]["value"] == 1
        # provenance still names the injected block on the fp16 path
        assert engine.numerics.snapshot()["nonfinite"]["last"][
            "block"] == "blk1"
    finally:
        engine.destroy()


def test_loss_spike_fires_flight_recorder_dump(tmp_path, fresh_telemetry):
    dump = str(tmp_path / "events.json")
    # goodput rides along: the two observers are armed together from
    # step one and neither disturbs the other's record
    engine = _make_engine(telemetry={"numerics_enabled": True,
                                     "goodput": True,
                                     "numerics_spike_window": 8,
                                     "events_dump_path": dump})
    try:
        for i in range(9):
            engine.train_batch(_batch(engine, seed=i))
        engine.train_batch(_batch(engine, y_offset=100.0))
        snap = engine.numerics.snapshot()
        assert snap["anomaly"]["total"] >= 1
        assert snap["anomaly"]["last"]["reason"] == "loss_spike"
        assert sorted(snap["blocks"]) == ["blk0", "blk1"]
        assert snap["nonfinite"]["steps_total"] == 0   # a spike, no NaN
        gp = engine.goodput.snapshot()
        assert gp["steps"] == 10
        assert gp["data_wait_s"] + gp["device_s"] + gp["host_s"] == \
            pytest.approx(gp["wall_s"], rel=1e-9)
        assert any(e["kind"] == "loss_spike"
                   for e in get_event_ring().snapshot())
        payload = json.load(open(dump + ".anomaly"))
        assert payload["dump_reason"] == "numerics_loss_spike"
        assert payload["source"] == "train"
        assert payload["events"]                     # the ring rode along
    finally:
        engine.destroy()


# ---------------------------------------------------------------------------
# goodput accounting
# ---------------------------------------------------------------------------

def test_goodput_buckets_sum_to_wall(fresh_telemetry):
    engine = _make_engine(telemetry={"goodput": True})
    try:
        for i in range(4):
            engine.train_batch(_batch(engine, seed=i))
        gp = engine.goodput.snapshot()
        assert gp["steps"] == 4
        total = gp["data_wait_s"] + gp["device_s"] + gp["host_s"]
        assert total == pytest.approx(gp["wall_s"], rel=1e-9)
        assert 0.0 < gp["fraction"] <= 1.0
        snap = engine.telemetry.snapshot()
        for name in ("train_goodput_step_wall_seconds",
                     "train_goodput_data_wait_seconds",
                     "train_goodput_device_seconds",
                     "train_goodput_host_seconds"):
            series = snap[name]["series"]
            assert len(series) == 1
            assert series[0]["labels"] == {"engine": "train"}
            assert series[0]["count"] == 4
        frac = snap["train_goodput_fraction"]["series"][0]["value"]
        assert frac == pytest.approx(gp["fraction"])
        # toggle off: recording stops, totals freeze
        engine.set_goodput_enabled(False)
        engine.train_batch(_batch(engine))
        assert engine.goodput.snapshot()["steps"] == 4
    finally:
        engine.destroy()


def test_goodput_off_by_default_records_nothing(fresh_telemetry):
    engine = _make_engine()
    try:
        engine.train_batch(_batch(engine))
        assert engine.goodput.snapshot()["steps"] == 0
        assert "train_goodput_step_wall_seconds" not in \
            engine.telemetry.snapshot()
    finally:
        engine.destroy()


# ---------------------------------------------------------------------------
# satellites: grad-norm contract, core scalars on the scrape surface
# ---------------------------------------------------------------------------

def test_get_global_grad_norm_contract(fresh_telemetry):
    engine = _make_engine()
    try:
        assert engine.get_global_grad_norm() is None   # before any step
        engine.train_batch(_batch(engine))
        g = engine.get_global_grad_norm()
        assert type(g) is float                        # host float, not
        assert not isinstance(g, jax.Array)            # a device array
        assert g > 0.0
    finally:
        engine.destroy()


def test_core_scalars_reach_scrape_surface(fresh_telemetry):
    engine = _make_engine()
    try:
        m = engine.train_batch(_batch(engine))
        snap = engine.telemetry.snapshot()
        assert snap["train_loss"]["series"][0]["value"] == \
            pytest.approx(float(m["loss"]))
        assert snap["train_lr"]["series"][0]["value"] == \
            pytest.approx(float(m["lr"]))
        assert snap["train_grad_norm"]["series"][0]["value"] == \
            pytest.approx(float(m["grad_norm"]))
        text = engine.telemetry.prometheus_text()
        assert "\ntrain_loss " in text
        assert "\ntrain_grad_norm " in text
    finally:
        engine.destroy()


def test_telemetry_config_validates_numerics_keys():
    from deepspeed_tpu.telemetry import TelemetryConfig
    cfg = TelemetryConfig(numerics_enabled=True, numerics_block_depth=2,
                          numerics_spike_window=16,
                          numerics_spike_threshold=4.0, goodput=True)
    assert cfg.numerics_block_depth == 2
    with pytest.raises(Exception):
        TelemetryConfig(numerics_block_depth=0)
    with pytest.raises(Exception):
        TelemetryConfig(numerics_spike_window=4)
    with pytest.raises(Exception):
        TelemetryConfig(numerics_spike_threshold=-1.0)
    # the inference schema shares the section (both schemas, one source)
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    icfg = DeepSpeedInferenceConfig(
        telemetry={"numerics_enabled": True, "goodput": True})
    assert icfg.telemetry.numerics_enabled is True
