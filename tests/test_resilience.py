"""Fault-tolerant training chaos suite.

The training mirror of tests/test_replicated_serving.py: verified atomic
checkpoints (manifest, fallback ladder, retention GC), the
TrainingSupervisor's crash/NaN/stall/preemption recovery, and the
headline oracle — a mid-run seeded kill (and separately a mid-save
kill) plus auto-resume produces a loss trajectory and final params
BIT-IDENTICAL to the undisturbed run. Fake clock / recorded sleeps —
zero real waiting anywhere.
"""
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.checkpoint.integrity import (atomic_write_json,
                                                committed_tags,
                                                read_manifest,
                                                verify_checkpoint)
from deepspeed_tpu.runtime.resilience import (TrainingFailed,
                                              TrainingSupervisor,
                                              resilience_snapshot)
from deepspeed_tpu.telemetry import (FaultInjector, MetricRegistry,
                                     get_event_ring)
from deepspeed_tpu.telemetry.faultinject import CkptWriteFault

D, O, B = 8, 4, 4


@pytest.fixture(autouse=True)
def _clean_ring():
    get_event_ring().clear()
    yield
    get_event_ring().clear()


def build_engine(tmpdir=None, resilience=None, checkpoint=None,
                 telemetry=None, fault_injection=None):
    rng = np.random.default_rng(3)
    params = {
        "blk0": {"w": jnp.asarray(rng.normal(0, 0.1, (D, D)), jnp.float32)},
        "blk1": {"w": jnp.asarray(rng.normal(0, 0.1, (D, O)), jnp.float32)},
    }

    def loss_fn(p, b, rng_):
        h = jnp.tanh(b["x"] @ p["blk0"]["w"])
        return jnp.mean((h @ p["blk1"]["w"] - b["y"]) ** 2)

    cfg = {"train_micro_batch_size_per_gpu": B, "steps_per_print": 1000,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
           "resilience": {"checkpoint_every": 2, "max_restarts": 3,
                          "backoff_base_s": 0.5, "backoff_max_s": 4.0,
                          **(resilience or {})}}
    if checkpoint:
        cfg["checkpoint"] = checkpoint
    if telemetry:
        cfg["telemetry"] = telemetry
    if fault_injection:
        cfg.setdefault("telemetry", {})["fault_injection"] = fault_injection
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=loss_fn, model_parameters=params, config=cfg)
    return engine


def batch_fn(step):
    # global batch = micro * dp (the conftest mesh has dp=8); a pure
    # function of the step — the supervisor's determinism contract
    gb = B * jax.device_count()
    rng = np.random.default_rng(500 + step)
    return {"x": jnp.asarray(rng.normal(size=(gb, D)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(gb, O)), jnp.float32)}


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        self.t += 0.001   # every read advances a tick (monotonic)
        return self.t


def make_supervisor(engine, save_dir, injector=None, **kw):
    """Fake clock + recorded (never slept) backoff."""
    clock = FakeClock()
    slept = []

    def sleep(s):
        slept.append(s)
        clock.t += s
    sup = TrainingSupervisor(engine, str(save_dir), batch_fn,
                             clock=clock, sleep=sleep, injector=injector,
                             **kw)
    sup._test_slept = slept
    sup._test_clock = clock
    return sup


def params_list(engine):
    return [np.asarray(jax.device_get(leaf))
            for leaf in jax.tree.leaves(engine.state.params)]


def run_undisturbed(tmp_path, steps=6, **build_kw):
    d = tmp_path / "base"
    engine = build_engine(**build_kw)
    sup = make_supervisor(engine, d)
    rec = sup.run(steps)
    assert rec["status"] == "completed"
    out = (rec, params_list(engine))
    sup.close()
    engine.destroy()
    return out


# ---------------------------------------------------------------------------
# checkpoint layer: atomic publication + strict meta
# ---------------------------------------------------------------------------

class TestAtomicPublish:
    def test_manifest_written_and_verifies(self, tmp_path):
        engine = build_engine()
        engine.train_batch(batch_fn(0))
        ckpt_dir = engine.save_checkpoint(str(tmp_path))
        ok, reason = verify_checkpoint(ckpt_dir)
        assert ok, reason
        m = read_manifest(ckpt_dir)
        assert m["step"] == 1 and m["files"]
        # every content file is covered, incl. client_state.json
        assert "client_state.json" in m["files"]
        with open(tmp_path / "latest") as f:
            assert f.read().strip() == os.path.basename(ckpt_dir)
        engine.destroy()

    def test_unserializable_client_state_raises_not_stringifies(
            self, tmp_path):
        engine = build_engine()
        engine.train_batch(batch_fn(0))
        with pytest.raises(TypeError, match="not JSON-serializable"):
            engine.save_checkpoint(str(tmp_path), tag="bad",
                                   client_state={"arr": object()})
        # 'latest' was never written — the failed publish is invisible
        assert not os.path.exists(tmp_path / "latest")
        engine.destroy()

    def test_no_tmp_debris_after_save(self, tmp_path):
        engine = build_engine()
        engine.train_batch(batch_fn(0))
        ckpt_dir = engine.save_checkpoint(str(tmp_path))
        for dirpath, _, files in os.walk(tmp_path):
            assert not [f for f in files if f.endswith(".tmp")], dirpath
        assert verify_checkpoint(ckpt_dir)[0]
        engine.destroy()

    def test_mid_save_kill_leaves_latest_on_previous_tag(self, tmp_path):
        engine = build_engine()
        inj = FaultInjector(seed=0, registry=engine.telemetry)
        engine.fault_injector = inj
        engine.train_batch(batch_fn(0))
        first = engine.save_checkpoint(str(tmp_path))
        engine.train_batch(batch_fn(1))
        inj.fail_next_ckpt_write()
        with pytest.raises(CkptWriteFault):
            engine.save_checkpoint(str(tmp_path))
        with open(tmp_path / "latest") as f:
            assert f.read().strip() == os.path.basename(first)
        # the half-written tag is manifest-less -> not a committed tag
        assert [t for _, t in committed_tags(str(tmp_path))] == \
            [os.path.basename(first)]
        # and a later clean re-save of the same tag publishes fine
        path2 = engine.save_checkpoint(str(tmp_path))
        assert verify_checkpoint(path2)[0]
        with open(tmp_path / "latest") as f:
            assert f.read().strip() == os.path.basename(path2)
        engine.destroy()

    def test_resave_of_committed_latest_demotes_latest_first(
            self, tmp_path):
        # a re-save INTO the committed tag 'latest' names invalidates
        # that tag's manifest before new bytes land — 'latest' must be
        # demoted to the previous good tag FIRST, or a crash mid-save
        # leaves it naming a torn, manifest-less dir
        engine = build_engine()
        inj = FaultInjector(seed=0, registry=engine.telemetry)
        engine.fault_injector = inj
        engine.train_batch(batch_fn(0))
        first = engine.save_checkpoint(str(tmp_path))    # global_step1
        engine.train_batch(batch_fn(1))
        newest = engine.save_checkpoint(str(tmp_path))   # global_step2
        inj.fail_next_ckpt_write()
        with pytest.raises(CkptWriteFault):
            engine.save_checkpoint(str(tmp_path),
                                   tag=os.path.basename(newest))
        with open(tmp_path / "latest") as f:
            assert f.read().strip() == os.path.basename(first)
        assert [t for _, t in committed_tags(str(tmp_path))] == \
            [os.path.basename(first)]
        path, _ = engine.load_checkpoint(str(tmp_path))
        assert path == first
        engine.destroy()
        # only committed tag: the crashed re-save drops the pointer
        # entirely rather than leave it naming the torn dir
        engine2 = build_engine()
        d2 = tmp_path / "solo"
        inj2 = FaultInjector(seed=0, registry=engine2.telemetry)
        engine2.fault_injector = inj2
        engine2.train_batch(batch_fn(0))
        solo = engine2.save_checkpoint(str(d2))
        inj2.fail_next_ckpt_write()
        with pytest.raises(CkptWriteFault):
            engine2.save_checkpoint(str(d2), tag=os.path.basename(solo))
        assert not os.path.exists(d2 / "latest")
        engine2.destroy()


# ---------------------------------------------------------------------------
# corruption matrix -> fallback ladder
# ---------------------------------------------------------------------------

def _save_two_tags(tmp_path, engine):
    engine.train_batch(batch_fn(0))
    good = engine.save_checkpoint(str(tmp_path))   # global_step1
    engine.train_batch(batch_fn(1))
    newest = engine.save_checkpoint(str(tmp_path))  # global_step2
    return good, newest


def _assert_falls_back(tmp_path, engine, good, expect_reason):
    ring_before = len([e for e in get_event_ring().snapshot()
                       if e["kind"] == "ckpt_fallback"])
    path, _ = engine.load_checkpoint(str(tmp_path))
    assert path == good
    assert engine.global_steps == 1   # the previous tag's step
    falls = [e for e in get_event_ring().snapshot()
             if e["kind"] == "ckpt_fallback"]
    assert len(falls) > ring_before
    assert any(e["data"]["reason"].startswith(expect_reason)
               for e in falls)


class TestCorruptionFallback:
    def test_flipped_byte_checksum_catches(self, tmp_path):
        engine = build_engine()
        inj = FaultInjector(seed=1, registry=engine.telemetry)
        good, newest = _save_two_tags(tmp_path, engine)
        inj.corrupt_checkpoint(newest)
        _assert_falls_back(tmp_path, engine, good, "checksum_mismatch")
        assert inj.injected["ckpt_corrupt"] == 1
        engine.destroy()

    def test_truncated_array_file(self, tmp_path):
        engine = build_engine()
        good, newest = _save_two_tags(tmp_path, engine)
        # truncate the largest state file
        files = []
        for dirpath, _, names in os.walk(os.path.join(newest, "state")):
            files += [os.path.join(dirpath, f) for f in names]
        victim = max(files, key=os.path.getsize)
        with open(victim, "r+b") as f:
            f.truncate(max(os.path.getsize(victim) // 2, 1))
        _assert_falls_back(tmp_path, engine, good, "size_mismatch")
        engine.destroy()

    def test_missing_manifest(self, tmp_path):
        engine = build_engine()
        good, newest = _save_two_tags(tmp_path, engine)
        os.unlink(os.path.join(newest, "manifest.json"))
        _assert_falls_back(tmp_path, engine, good, "missing_manifest")
        engine.destroy()

    def test_missing_file(self, tmp_path):
        engine = build_engine()
        good, newest = _save_two_tags(tmp_path, engine)
        os.unlink(os.path.join(newest, "client_state.json"))
        _assert_falls_back(tmp_path, engine, good, "missing_file")
        engine.destroy()

    def test_stale_latest_points_at_deleted_tag(self, tmp_path):
        engine = build_engine()
        good, newest = _save_two_tags(tmp_path, engine)
        import shutil
        shutil.rmtree(newest)
        # 'latest' still names the deleted tag
        with open(tmp_path / "latest") as f:
            assert f.read().strip() == os.path.basename(newest)
        _assert_falls_back(tmp_path, engine, good, "missing_dir")
        engine.destroy()

    def test_loaded_fallback_params_match_good_tag(self, tmp_path):
        engine = build_engine()
        good, newest = _save_two_tags(tmp_path, engine)
        at_good = params_list(engine)  # wrong — engine is at step 2
        # capture the good tag's params via a clean load first
        fresh = build_engine()
        fresh.load_checkpoint(str(tmp_path), tag=os.path.basename(good))
        at_good = params_list(fresh)
        FaultInjector(seed=2).corrupt_checkpoint(newest)
        engine.load_checkpoint(str(tmp_path))
        for a, b in zip(params_list(engine), at_good):
            np.testing.assert_array_equal(a, b)
        fresh.destroy()
        engine.destroy()

    def test_explicit_tag_corrupt_raises_never_substitutes(self, tmp_path):
        # a caller-pinned tag that fails verification must RAISE — a
        # reproducibility run must never be silently handed an older
        # checkpoint than the one it pinned (tag=None gets the ladder)
        engine = build_engine()
        good, newest = _save_two_tags(tmp_path, engine)
        FaultInjector(seed=5).corrupt_checkpoint(newest)
        with pytest.raises(RuntimeError, match="silently substitute"):
            engine.load_checkpoint(str(tmp_path),
                                   tag=os.path.basename(newest))
        engine.destroy()

    def test_every_tag_corrupt_raises_never_garbage(self, tmp_path):
        engine = build_engine()
        good, newest = _save_two_tags(tmp_path, engine)
        inj = FaultInjector(seed=3)
        inj.corrupt_checkpoint(good)
        inj.corrupt_checkpoint(newest)
        with pytest.raises(RuntimeError, match="refusing to restore"):
            engine.load_checkpoint(str(tmp_path))
        engine.destroy()

    def test_verify_failures_counted_by_reason(self, tmp_path):
        reg = MetricRegistry()
        engine = build_engine()
        engine.telemetry = reg
        good, newest = _save_two_tags(tmp_path, engine)
        os.unlink(os.path.join(newest, "manifest.json"))
        engine.load_checkpoint(str(tmp_path))
        snap = reg.snapshot()["ckpt_verify_failures_total"]
        series = {tuple(sorted(s["labels"].items())): s["value"]
                  for s in snap["series"]}
        assert series[(("reason", "missing_manifest"),)] == 1
        engine.destroy()


# ---------------------------------------------------------------------------
# retention GC
# ---------------------------------------------------------------------------

class TestRetention:
    def test_keep_last_bounds_tags_and_counts_bytes(self, tmp_path):
        reg = MetricRegistry()
        engine = build_engine(checkpoint={"keep_last": 2})
        engine.telemetry = reg
        for s in range(4):
            engine.train_batch(batch_fn(s))
            engine.save_checkpoint(str(tmp_path))
        tags = [t for _, t in committed_tags(str(tmp_path))]
        assert tags == ["global_step4", "global_step3"]
        gc = reg.snapshot()["ckpt_gc_reclaimed_total"]["series"][0]
        assert gc["value"] > 0
        assert any(e["kind"] == "ckpt_gc"
                   for e in get_event_ring().snapshot())
        # 'latest' still verifies after GC
        path, _ = engine.load_checkpoint(str(tmp_path))
        assert os.path.basename(path) == "global_step4"
        engine.destroy()

    def test_keep_last_zero_keeps_everything(self, tmp_path):
        engine = build_engine()
        for s in range(3):
            engine.train_batch(batch_fn(s))
            engine.save_checkpoint(str(tmp_path))
        assert len(committed_tags(str(tmp_path))) == 3
        engine.destroy()


# ---------------------------------------------------------------------------
# async finalize: teardown paths + double finalize / orphan tmp pins
# ---------------------------------------------------------------------------

class TestAsyncFinalize:
    def test_destroy_joins_pending_finalize(self, tmp_path):
        engine = build_engine(checkpoint={"engine": "async"})
        engine.train_batch(batch_fn(0))
        engine.save_checkpoint(str(tmp_path))
        engine.destroy()   # must join — 'latest' durable afterwards
        assert getattr(engine, "_ckpt_finalize_thread", None) is None
        with open(tmp_path / "latest") as f:
            tag = f.read().strip()
        assert verify_checkpoint(str(tmp_path / tag))[0]

    def test_destroy_surfaces_failed_finalize(self, tmp_path):
        engine = build_engine(checkpoint={"engine": "async"})
        inj = FaultInjector(seed=0, registry=engine.telemetry)
        engine.fault_injector = inj
        engine.train_batch(batch_fn(0))
        inj.fail_next_ckpt_write()
        engine.save_checkpoint(str(tmp_path))
        with pytest.raises(RuntimeError, match="finalize failed"):
            engine.destroy()
        assert not os.path.exists(tmp_path / "latest")
        # the raise came AFTER full teardown: executables dropped, the
        # checkpoint engine released (no leaked scrape port / threads)
        assert engine._step_fn is None
        assert engine._ckpt_engine is None
        # error is one-shot: a second destroy is clean (double-finalize
        # / double-join pin)
        engine.destroy()

    def test_destroy_survives_ckpt_engine_close_failure(self, tmp_path):
        # ce.close() raising inside destroy's finally must not abort
        # the rest of teardown (port/monitor/watchdog would leak) —
        # the error surfaces AFTER, like a stashed finalize failure
        engine = build_engine(checkpoint={"engine": "async"})
        engine.train_batch(batch_fn(0))
        engine.save_checkpoint(str(tmp_path))
        ce = engine._ckpt_engine
        assert ce is not None

        def boom():
            raise OSError("close blew up")
        ce.close = boom
        with pytest.raises(RuntimeError, match="close failed"):
            engine.destroy()
        assert engine._step_fn is None
        assert engine._ckpt_engine is None
        assert engine._telemetry_http is None
        engine.destroy()   # second destroy clean

    def test_failed_async_finalize_surfaces_at_next_save(self, tmp_path):
        engine = build_engine(checkpoint={"engine": "async"})
        inj = FaultInjector(seed=0, registry=engine.telemetry)
        engine.fault_injector = inj
        engine.train_batch(batch_fn(0))
        inj.fail_next_ckpt_write()
        engine.save_checkpoint(str(tmp_path))
        with pytest.raises(RuntimeError, match="finalize failed"):
            engine.save_checkpoint(str(tmp_path))
        # the retry save then publishes cleanly over the debris
        path = engine.save_checkpoint(str(tmp_path))
        import deepspeed_tpu.runtime.checkpointing as ckpt_mod
        ckpt_mod._join_pending_finalize(engine)
        assert verify_checkpoint(path)[0]
        engine.destroy()

    def test_orphan_tmp_files_ignored_and_cleaned(self, tmp_path):
        engine = build_engine()
        engine.train_batch(batch_fn(0))
        ckpt_dir = engine.save_checkpoint(str(tmp_path))
        # orphan tmp debris from a hypothetical crashed atomic write
        orphan = os.path.join(ckpt_dir, "client_state.json.tmp")
        with open(orphan, "w") as f:
            f.write("debris")
        ok, reason = verify_checkpoint(ckpt_dir)
        assert ok, reason   # tmp files are never manifest content
        # a re-save of the same tag clears the debris
        engine.save_checkpoint(
            str(tmp_path), tag=os.path.basename(ckpt_dir))
        assert not os.path.exists(orphan)
        engine.destroy()


# ---------------------------------------------------------------------------
# supervisor: the recovery oracle
# ---------------------------------------------------------------------------

class TestSupervisorOracle:
    STEPS = 6

    def _chaos_run(self, tmp_path, injector, steps=None, **sup_kw):
        d = tmp_path / "chaos"
        engine = build_engine()
        sup = make_supervisor(engine, d, injector=injector, **sup_kw)
        rec = sup.run(steps or self.STEPS)
        out = (rec, params_list(engine), sup)
        engine.destroy()
        return out

    def test_mid_run_kill_bit_identical(self, tmp_path):
        base, base_params = run_undisturbed(tmp_path, self.STEPS)
        inj = FaultInjector(seed=0, step_crash_step=3)
        rec, params, sup = self._chaos_run(tmp_path, inj)
        assert rec["status"] == "completed"
        assert rec["restarts"] == 1
        assert [f["kind"] for f in rec["faults"]] == ["step_crash"]
        assert rec["losses"] == base["losses"]
        for a, b in zip(params, base_params):
            np.testing.assert_array_equal(a, b)
        # fault + resume bracket the restart in the ring
        kinds = [e["kind"] for e in get_event_ring().snapshot()]
        assert "train_fault" in kinds and "train_resume" in kinds
        sup.close()

    def test_seeded_preemption_bit_identical(self, tmp_path):
        base, base_params = run_undisturbed(tmp_path, self.STEPS)
        engine = build_engine(
            fault_injection={"enabled": True, "preempt_step": 4})
        sup = make_supervisor(engine, tmp_path / "c2")
        assert sup.injector is engine.fault_injector  # config-armed
        rec = sup.run(self.STEPS)
        assert rec["status"] == "completed"
        assert [f["kind"] for f in rec["faults"]] == ["preempt_step"]
        assert rec["losses"] == base["losses"]
        for a, b in zip(params_list(engine), base_params):
            np.testing.assert_array_equal(a, b)
        sup.close()
        engine.destroy()

    def test_mid_save_kill_bit_identical(self, tmp_path):
        base, base_params = run_undisturbed(tmp_path, self.STEPS)
        inj = FaultInjector(seed=0)
        inj.ckpt_write_failure_save = 3   # the step-4 boundary save dies
        rec, params, sup = self._chaos_run(tmp_path, inj)
        assert rec["status"] == "completed"
        assert [f["kind"] for f in rec["faults"]] == ["ckpt_write_failure"]
        assert rec["losses"] == base["losses"]
        for a, b in zip(params, base_params):
            np.testing.assert_array_equal(a, b)
        sup.close()

    def test_preemption_and_mid_save_kill_in_one_run_under_retention(
            self, tmp_path):
        # two faults in one run while retention keeps only two tags: the
        # half-written tag must neither be restored from nor push the
        # last good one out of the window
        keep = {"keep_last": 2}
        base, base_params = run_undisturbed(tmp_path, self.STEPS,
                                            checkpoint=keep)
        engine = build_engine(checkpoint=keep)
        inj = FaultInjector(seed=0, preempt_step=3)
        inj.ckpt_write_failure_save = 3
        sup = make_supervisor(engine, tmp_path / "two", injector=inj)
        rec = sup.run(self.STEPS)
        assert rec["status"] == "completed"
        assert rec["restarts"] == 2
        assert sorted(f["kind"] for f in rec["faults"]) == [
            "ckpt_write_failure", "preempt_step"]
        assert rec["losses"] == base["losses"]
        for a, b in zip(params_list(engine), base_params):
            np.testing.assert_array_equal(a, b)
        assert len(rec["checkpoint_integrity"]["tags"]) == 2
        assert rec["recovery_s_total"] > 0
        assert 0.0 < rec["goodput_under_chaos"] <= 1.0
        sup.close()
        engine.destroy()

    def test_nan_burst_detected_and_bit_identical(self, tmp_path):
        base, base_params = run_undisturbed(tmp_path, self.STEPS)
        inj = FaultInjector(seed=0, nan_burst_step=3)
        rec, params, sup = self._chaos_run(tmp_path, inj)
        assert rec["status"] == "completed"
        assert [f["kind"] for f in rec["faults"]] == ["nan_burst"]
        assert rec["losses"] == base["losses"]
        assert all(np.isfinite(l) for l in rec["losses"])
        for a, b in zip(params, base_params):
            np.testing.assert_array_equal(a, b)
        sup.close()

    def test_nan_burst_via_numerics_watch(self, tmp_path):
        # with the in-graph observatory armed the SAME burst is caught
        # with per-block provenance riding the ring — and recovery still
        # replays bit-identically
        d = tmp_path / "nw"
        engine = build_engine(telemetry={"numerics_enabled": True})
        sup = make_supervisor(engine, d,
                              injector=FaultInjector(seed=0,
                                                     nan_burst_step=2))
        rec = sup.run(4)
        assert rec["status"] == "completed"
        assert [f["kind"] for f in rec["faults"]] == ["nan_burst"]
        kinds = [e["kind"] for e in get_event_ring().snapshot()]
        assert "numerics_nonfinite" in kinds
        sup.close()
        engine.destroy()

    def test_async_engine_completed_means_durable(self, tmp_path):
        # run() must not claim "completed" while an async terminal
        # finalize is still in flight — the status joins it first
        d = tmp_path / "async"
        engine = build_engine(checkpoint={"engine": "async"})
        sup = make_supervisor(engine, d)
        rec = sup.run(4)
        assert rec["status"] == "completed"
        with open(d / "latest") as f:
            tag = f.read().strip()
        assert tag == "global_step4"
        assert verify_checkpoint(str(d / tag))[0]
        assert rec["checkpoint_integrity"]["latest_committed"] is True
        sup.close()
        engine.destroy()

    def test_async_ckpt_write_failure_classified_not_step_crash(
            self, tmp_path):
        # the stashed CkptWriteFault resurfaces as `RuntimeError from
        # CkptWriteFault` at the next save's join — the restart counter
        # must still say ckpt_write_failure (cause-chain unwrap)
        base, base_params = run_undisturbed(tmp_path, self.STEPS)
        d = tmp_path / "ac"
        engine = build_engine(checkpoint={"engine": "async"})
        inj = FaultInjector(seed=0)
        inj.ckpt_write_failure_save = 3
        sup = make_supervisor(engine, d, injector=inj)
        rec = sup.run(self.STEPS)
        assert rec["status"] == "completed"
        assert [f["kind"] for f in rec["faults"]] == ["ckpt_write_failure"]
        assert rec["losses"] == base["losses"]
        for a, b in zip(params_list(engine), base_params):
            np.testing.assert_array_equal(a, b)
        sup.close()
        engine.destroy()

    def test_data_stall_injected(self, tmp_path):
        base, base_params = run_undisturbed(tmp_path, self.STEPS)
        inj = FaultInjector(seed=0, data_stall_step=2)
        rec, params, sup = self._chaos_run(tmp_path, inj)
        assert rec["status"] == "completed"
        assert [f["kind"] for f in rec["faults"]] == ["data_stall"]
        assert rec["losses"] == base["losses"]
        sup.close()

    def test_data_stall_real_timeout_fake_clock(self, tmp_path):
        d = tmp_path / "ds"
        engine = build_engine(
            resilience={"data_stall_timeout_s": 5.0})
        sup = make_supervisor(engine, d)
        stalled = {"done": False}
        real_fn = batch_fn

        def slow_batch(step):
            if step == 2 and not stalled["done"]:
                stalled["done"] = True
                sup._test_clock.t += 60.0   # fetch "took" 60 fake secs
            return real_fn(step)
        sup.batch_fn = slow_batch
        rec = sup.run(4)
        assert rec["status"] == "completed"
        assert [f["kind"] for f in rec["faults"]] == ["data_stall"]
        sup.close()
        engine.destroy()

    def test_batch_fn_never_entered_concurrently_across_stall(
            self, tmp_path):
        # regression: the per-fetch thread spawn re-entered batch_fn
        # concurrently with a still-blocked abandoned fetch after a
        # DataStall — UB for any shared-iterator data pipeline. The
        # persistent worker serializes every call (the replay queues
        # BEHIND the outstanding fetch) and a transient stall recovers.
        import threading
        engine = build_engine(
            resilience={"data_stall_timeout_s": 0.2, "max_restarts": 5,
                        "backoff_base_s": 0.0})
        sup = make_supervisor(engine, tmp_path / "conc")
        gate = threading.Event()
        lock = threading.Lock()
        state = {"active": 0, "max_active": 0, "stalled": False}

        def guarded(step):
            with lock:
                state["active"] += 1
                state["max_active"] = max(state["max_active"],
                                          state["active"])
            try:
                if step == 2 and not state["stalled"]:
                    state["stalled"] = True
                    threading.Timer(0.3, gate.set).start()
                    gate.wait()   # blocks past the 0.2s bound
                return batch_fn(step)
            finally:
                with lock:
                    state["active"] -= 1
        sup.batch_fn = guarded
        rec = sup.run(4)
        assert rec["status"] == "completed"
        assert "data_stall" in [f["kind"] for f in rec["faults"]]
        assert state["max_active"] == 1
        sup.close()
        engine.destroy()


# ---------------------------------------------------------------------------
# supervisor: budget, backoff, failure semantics
# ---------------------------------------------------------------------------

class TestSupervisorBudget:
    def test_retries_exhausted_ends_failed_never_hangs(self, tmp_path):
        engine = build_engine(resilience={"max_restarts": 2})
        inj = FaultInjector(seed=0)
        for s in (1, 2, 3):
            inj.crash_at(s)
        sup = make_supervisor(engine, tmp_path / "f", injector=inj)
        rec = sup.run(6)
        assert rec["status"] == "failed"
        # only actual rollbacks count — the terminal fault never
        # restarts, so the counter stays bounded by max_restarts
        assert rec["restarts"] == 2
        assert rec["faults"][-1]["restart"] == 3   # the attempt number
        assert "restart budget exhausted" in rec["failure"]
        assert len(rec["faults"]) == 3
        # exponential backoff, recorded not slept: 0.5, 1.0 (the third
        # fault exhausts the budget before any backoff)
        assert sup._test_slept == [0.5, 1.0]
        sup.close()
        engine.destroy()

    def test_backoff_capped_at_max(self, tmp_path):
        engine = build_engine(
            resilience={"max_restarts": 5, "backoff_base_s": 1.0,
                        "backoff_max_s": 2.5})
        inj = FaultInjector(seed=0)
        for s in (1, 2, 3, 4):
            inj.crash_at(s)
        sup = make_supervisor(engine, tmp_path / "b", injector=inj)
        rec = sup.run(6)
        assert rec["status"] == "completed"
        assert sup._test_slept == [1.0, 2.0, 2.5, 2.5]
        sup.close()
        engine.destroy()

    def test_raise_on_failure(self, tmp_path):
        engine = build_engine(resilience={"max_restarts": 0})
        inj = FaultInjector(seed=0, step_crash_step=1)
        sup = make_supervisor(engine, tmp_path / "r", injector=inj)
        with pytest.raises(TrainingFailed, match="budget exhausted"):
            sup.run(4, raise_on_failure=True)
        assert sup.status == "failed"
        sup.close()
        engine.destroy()

    def test_recovery_metrics_and_restart_counter(self, tmp_path):
        engine = build_engine()
        reg = engine.telemetry = MetricRegistry()
        inj = FaultInjector(seed=0, step_crash_step=2,
                            registry=reg)
        sup = make_supervisor(engine, tmp_path / "m", injector=inj)
        sup.registry = reg
        rec = sup.run(4)
        assert rec["status"] == "completed"
        snap = reg.snapshot()
        restarts = snap["train_restarts_total"]["series"]
        assert {tuple(s["labels"].items()): s["value"]
                for s in restarts} == {(("kind", "step_crash"),): 1}
        recov = snap["train_recovery_seconds"]["series"][0]
        assert recov["count"] == 1 and recov["sum"] > 0
        assert rec["recovery_s_total"] > 0
        assert 0.0 < rec["goodput_under_chaos"] <= 1.0
        sup.close()
        engine.destroy()

    def test_rollback_skips_corrupted_newest_tag(self, tmp_path):
        # fault at step 5; the newest checkpoint (step 4) is corrupted
        # on disk -> recovery lands on step 2's tag and still completes
        # bit-identically
        base, base_params = run_undisturbed(tmp_path, 6)
        d = tmp_path / "cor"
        engine = build_engine()
        inj = FaultInjector(seed=4)
        sup = make_supervisor(engine, d, injector=inj)

        orig_check = inj.check_train_step
        armed = {"done": False}

        def check(step):
            if step == 5 and not armed["done"]:
                armed["done"] = True
                inj.corrupt_checkpoint(str(d / "global_step4"))
                inj.crash_at(5)
            orig_check(step)
        inj.check_train_step = check
        rec = sup.run(6)
        assert rec["status"] == "completed"
        assert rec["losses"] == base["losses"]
        for a, b in zip(params_list(engine), base_params):
            np.testing.assert_array_equal(a, b)
        falls = [e for e in get_event_ring().snapshot()
                 if e["kind"] == "ckpt_fallback"]
        assert any(e["data"]["tag"] == "global_step4" for e in falls)
        sup.close()
        engine.destroy()


# ---------------------------------------------------------------------------
# surfaces: snapshot, /debug/resilience
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_snapshot_and_registry(self, tmp_path):
        engine = build_engine()
        inj = FaultInjector(seed=0, step_crash_step=2)
        sup = make_supervisor(engine, tmp_path / "s", injector=inj)
        rec = sup.run(4)
        snap = sup.snapshot()
        assert snap["status"] == "completed"
        assert snap["restarts"] == 1
        assert snap["checkpoint_integrity"]["latest_committed"] is True
        assert snap["fault_injection"]["injected"]["step_crash"] == 1
        assert json.loads(json.dumps(rec, default=str))  # JSON-able
        live = resilience_snapshot()
        assert live["enabled"] and any(
            s["restarts"] == 1 for s in live["supervisors"])
        sup.close()
        assert resilience_snapshot()["enabled"] is False
        engine.destroy()

    def test_debug_resilience_route_over_http(self, tmp_path):
        from deepspeed_tpu.telemetry import start_http_server
        engine = build_engine()
        sup = make_supervisor(engine, tmp_path / "h")
        sup.run(2)
        srv = start_http_server(0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/debug/resilience",
                    timeout=10) as resp:
                payload = json.loads(resp.read())
            assert payload["enabled"] is True
            assert payload["supervisors"][0]["status"] == "completed"
            # route is listed on the help page
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/", timeout=10) as resp:
                assert b"/debug/resilience" in resp.read()
        finally:
            srv.close()
        sup.close()
        engine.destroy()


# ---------------------------------------------------------------------------
# watchdog suspension + rng round-trip details
# ---------------------------------------------------------------------------

class TestPlumbing:
    def test_watchdog_suspend_scope(self):
        from deepspeed_tpu.telemetry.watchdog import Watchdog
        t = {"now": 0.0}
        wd = Watchdog(deadline_s=10.0, registry=MetricRegistry(),
                      clock=lambda: t["now"])
        wd.notify_progress()
        with wd.suspend():
            t["now"] = 100.0          # way past the deadline
            assert wd.check() is False   # suspended: never fires
        assert wd.check() is False       # exit counted as progress
        t["now"] = 200.0
        assert wd.check() is True        # deadline live again
        # nested: inner exit does not re-arm
        wd.notify_progress()
        with wd.suspend():
            with wd.suspend():
                pass
            t["now"] = 400.0
            assert wd.check() is False
        assert wd.stalls == 1

    def test_watchdog_disarm_during_suspend_stays_disarmed(self):
        # teardown racing an active suspension: the suspend exit's
        # restore of the entry-time flag must not resurrect a watchdog
        # its owner disarmed mid-suspension
        from deepspeed_tpu.telemetry.watchdog import Watchdog
        t = {"now": 0.0}
        wd = Watchdog(deadline_s=10.0, registry=MetricRegistry(),
                      clock=lambda: t["now"])
        wd.notify_progress()
        with wd.suspend():
            wd.disarm()
        t["now"] = 100.0
        assert wd.check() is False
        assert wd.stalls == 0

    def test_rng_typed_key_round_trip(self, tmp_path):
        # an engine carrying a TYPED PRNG key must get a typed key of
        # the SAME impl back at restore — a raw uint32 array would
        # crash split() or silently draw a different stream
        engine = build_engine()
        engine.train_batch(batch_fn(0))
        engine._rng = jax.random.key(7)
        engine.save_checkpoint(str(tmp_path))
        saved = np.asarray(jax.random.key_data(engine._rng))
        engine._rng = jax.random.key(99)
        engine.load_checkpoint(str(tmp_path))
        restored = engine._rng
        assert jax.dtypes.issubdtype(restored.dtype, jax.dtypes.prng_key)
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(restored)), saved)
        engine.destroy()

    def test_supervisor_injector_wins_over_config_injector(self, tmp_path):
        # a supervisor-scoped injector must reach the checkpoint write
        # site even when the engine built its own from config — split
        # brains would let armed ckpt_write_failure faults never fire
        engine = build_engine(
            fault_injection={"enabled": True, "seed": 9})
        assert engine.fault_injector is not None
        mine = FaultInjector(seed=0)
        mine.ckpt_write_failure_save = 2   # terminal save, recoverable
        sup = make_supervisor(engine, tmp_path / "inj", injector=mine)
        assert engine.fault_injector is mine
        rec = sup.run(2)
        assert rec["status"] == "completed"
        assert [f["kind"] for f in rec["faults"]] == ["ckpt_write_failure"]
        sup.close()
        engine.destroy()

    def test_rng_stream_restored_on_load(self, tmp_path):
        engine = build_engine()
        engine.train_batch(batch_fn(0))
        engine.save_checkpoint(str(tmp_path))
        rng_at_save = np.asarray(jax.device_get(engine._rng))
        engine.train_batch(batch_fn(1))   # advances the stream
        assert not np.array_equal(
            np.asarray(jax.device_get(engine._rng)), rng_at_save)
        engine.load_checkpoint(str(tmp_path))
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(engine._rng)), rng_at_save)
        engine.destroy()

    def test_keep_last_without_verify_rejected_at_config(self):
        # retention GC walks committed (manifest-bearing) tags — with
        # verify=false no manifest is ever written and keep_last would
        # silently never delete anything; the combination must be loud
        from deepspeed_tpu.config.config import CheckpointConfig
        with pytest.raises(Exception, match="keep_last requires"):
            CheckpointConfig(verify=False, keep_last=2)
        CheckpointConfig(verify=False, keep_last=0)   # inertless: fine

    def test_atomic_write_json_strict(self, tmp_path):
        p = str(tmp_path / "x.json")
        with pytest.raises(TypeError, match="not JSON-serializable"):
            atomic_write_json(p, {"bad": object()})
        assert not os.path.exists(p)
        assert not os.path.exists(p + ".tmp") or \
            os.path.getsize(p + ".tmp") == 0
