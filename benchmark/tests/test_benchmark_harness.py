"""CPU tests of the benchmark: counts and control flow only, never a
time. They call the harness's functions with the tiny configuration in
``benchmark/testdata``; nothing here makes the benchmark command itself
run anywhere but on a TPU.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "jax" not in sys.modules:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark.lib import (flops, harness, peaks, trace_reduce as tr,  # noqa: E402
                           traffic as T)

CONTRACT = harness.load_contract()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in CONTRACT["workloads"]]


def _twins_of(kind):
    """``testdata/<kind>/*.json`` by the name each says it is the tiny
    twin of (its ``twin_of`` key)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "testdata", kind,
                                              "*.json"))):
        twin_of = harness.load_json(path).get("twin_of")
        if twin_of:
            out[twin_of] = os.path.basename(path)[:-len(".json")]
    return out


# every cell of BENCHMARK.json is rehearsed through the tiny twins of its
# configuration and its traffic: a new cell brings testdata files that
# name what they are twins of, and edits nothing here
TWIN_CONFIGS, TWIN_TRAFFIC = _twins_of("configs"), _twins_of("traffic")
TWINS = {w["name"]: (TWIN_CONFIGS.get(w["config"]),
                     TWIN_TRAFFIC.get(w["traffic"]), w["chips"])
         for w in CONTRACT["workloads"]}


# ------------------------------------------------------------ the contract

def test_contract_shape():
    assert set(CONTRACT) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= CONTRACT["run_seconds"] <= 51
    e2e = {m["name"]: m for m in CONTRACT["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in CONTRACT["end_to_end"]
             + CONTRACT["per_layer"]]
    assert len(names) == len(set(names))
    for m in CONTRACT["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in CONTRACT["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        # the moved metric is reported wherever this one is
        for w in m.get("workloads", CELLS):
            assert w in e2e[m["moves"]].get("workloads", CELLS)
    for m in CONTRACT["end_to_end"] + CONTRACT["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
        assert all(w in CELLS for w in m.get("workloads", []))
    four = [w for w in CONTRACT["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in CONTRACT["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in CONTRACT["workloads"] + CONTRACT["configs"]:
        assert NAME.match(w["name"]) and 1 <= len(w["why"]) <= 200
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_every_file_by_name(workload):
    cell = harness.resolve_cell(CONTRACT, workload)
    assert cell["config"]["kind"] in ("train", "serve")
    assert cell["traffic"]["kind"] in ("train_job", "backlog", "open_loop")
    harness.load_family(cell["config"]["model"]["family"], cell["root"])
    assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
    assert cell["per_layer"]
    for name in cell["end_to_end"] + cell["per_layer"]:
        assert callable(harness.load_reader(name, cell["root"]))
    cfg = [c for c in CONTRACT["configs"]
           if c["name"] == cell["cell"]["config"]][0]
    assert cfg["file"].startswith(tuple(p + "/" for p in CONTRACT["paths"]))


# ----------------------------------------------------------------- traffic

def _traffic(name):
    return harness.load_json(os.path.join(BENCH, "traffic", name + ".json"))


@pytest.mark.parametrize("name", ["batch", "chat-p80"])
def test_multiset_is_the_same_for_every_seed_and_the_order_is_not(name):
    tr_ = _traffic(name)
    a = T.build_requests(tr_, 40, 1, 50257)
    b = T.build_requests(tr_, 40, 2 ** 31 + 11, 50257)

    def ms(r, counted):
        return sorted((len(x["prompt"]), x["out"]) for x in r["requests"]
                      if x["counted"] == counted)
    assert ms(a, True) == ms(b, True) and ms(a, False) == ms(b, False)
    assert a["totals"] == b["totals"]
    order = lambda r: [(len(x["prompt"]), x["out"])  # noqa: E731
                       for x in r["requests"]]
    assert order(a) != order(b)
    assert a["requests"][0]["prompt"] != b["requests"][0]["prompt"]
    assert all(len(x["prompt"]) + x["out"] <= tr_["max_total_tokens"]
               for x in a["requests"])
    # same seed, same inputs
    again = T.build_requests(tr_, 40, 1, 50257)
    assert [x["prompt"] for x in again["requests"]] == \
        [x["prompt"] for x in a["requests"]]


def test_any_stretch_of_the_order_is_close_to_the_whole():
    tr_ = _traffic("batch")
    reqs = T.build_requests(tr_, 40, 5, 50257)["requests"]
    per = sum(r["out"] for r in reqs) / len(reqs)
    for j in range(0, len(reqs) - 48, 48):
        part = sum(r["out"] for r in reqs[j:j + 48]) / 48
        assert abs(part - per) / per < 0.08


@pytest.mark.parametrize("process", [{"process": "poisson"},
                                     {"process": "exponential"},
                                     {"process": "gamma", "cv": 3.0}])
def test_open_loop_has_exactly_n_arrivals_inside_the_window(process):
    tr_ = dict(_traffic("chat-p80"), arrivals=process, rate_per_s=6.5,
               lead_in_s=8, order="permutation")
    for seed in (3, 2 ** 31 + 5):
        reqs = T.build_requests(tr_, 40, seed, 50257)["requests"]
        inside = [r for r in reqs if 0 <= r["due"] < 40]
        lead = [r for r in reqs if -8 <= r["due"] < 0]
        assert len(inside) == 260 and all(r["counted"] for r in inside)
        assert len(lead) == 52 and not any(r["counted"] for r in lead)
        assert len(inside) + len(lead) == len(reqs)
        due = [r["due"] for r in reqs]
        assert due == sorted(due)


def test_quantiles_follow_the_distributions():
    d = {"dist": "lognormal", "median": 128, "sigma": 0.9, "lo": 16,
         "hi": 768}
    assert T.quantile(d, 0.5) == 128
    assert T.quantile(d, 1e-6) == 16 and T.quantile(d, 1 - 1e-6) == 768
    lu = {"dist": "loguniform", "lo": 64, "hi": 512}
    assert T.quantile(lu, 0.5) == round((64 * 512) ** 0.5)
    pairs = T.multiset(1000, d, {"dist": "fixed", "value": 7}, 1024)
    assert abs(np.mean([p for p, _ in pairs]) - 183) < 4   # clipped mean


def test_shared_prefix_is_data():
    tr_ = dict(_traffic("chat-p80"), shared_prefix_tokens=32,
               shared_prefix_groups=2)
    reqs = T.build_requests(tr_, 10, 1, 50257)["requests"]
    heads = {tuple(r["prompt"][:32]) for r in reqs if len(r["prompt"]) >= 32}
    assert len(heads) == 2


# ------------------------------------------------------- peaks and FLOPs

def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9) and p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_flops_hand_worked_gpt2_1_3b_seq_1024():
    E, L, F, V, S = 2048, 24, 8192, 50257, 1024
    # per layer: qkv 3*E*E + out E*E + mlp 2*E*F = 50,331,648 weights
    assert flops.matmul_params(E, L, F) == 24 * 50_331_648 == 1_207_959_552
    # dense: 2 * (1,207,959,552 + 2048*50257) = 2,621,771,776
    # attention: 24 * 4 * 2048 * 1025/2 = 100,761,600
    fwd = flops.forward_flops_per_token(E, L, F, V, S)
    assert fwd == 2_621_771_776 + 100_761_600 == 2_722_533_376
    assert flops.train_flops_per_token(E, L, F, V, S) == 8_167_600_128
    # 4990.3 tokens/s (ledger, PR 22) is then 20.69 % of 197 TFLOP/s
    assert abs(100 * 8_167_600_128 * 4990.3 / 197e12 - 20.69) < 0.01
    # one causal flash call, 2 sequences x 16 heads x 1024 x 128
    pairs = 1024 * 1025 / 2
    assert flops.flash_flops(2, 16, 1024, 1024, 128) == \
        2 * 16 * pairs * 4 * 128 == 8_598_323_200
    assert flops.flash_flops(2, 16, 1024, 1024, 128, backward=True) == \
        3 * 8_598_323_200
    # bytes: q, k, v, o of 2*16*1024*128 bf16 = 8,388,608 B each
    assert flops.flash_bytes(2, 16, 1024, 1024, 128) == 4 * 8_388_608
    assert flops.flash_bytes(2, 16, 1024, 1024, 128, backward=True) == \
        (4 + 5 + 3) * 8_388_608
    # the forward call is compute-bound on a v5e: 43.6 us against 41.0 us
    p = peaks.peaks_for("TPU v5 lite")
    assert flops.roofline_seconds(8_598_323_200, 4 * 8_388_608, p) == \
        8_598_323_200 / 197e12
    # K and V of one position: 2 * 24 * 16 * 128 * 2 B
    assert flops.kv_bytes_per_token(24, 16, 128) == 196_608
    # a decode call over 48 slots of 512 live positions reads 201 MB/layer
    assert flops.paged_decode_bytes(48 * 512, 16, 128) == 201_326_592


# ------------------------------------------------------------------ traces

def test_interval_arithmetic():
    assert tr.merge([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == \
        [(0, 3), (5, 7)]
    assert tr.total([(0, 3), (5, 7)]) == 5
    assert tr.gaps([(0, 3), (5, 7)], -1, 10) == [(-1, 0), (3, 5), (7, 10)]
    assert tr.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]


def test_self_time_takes_children_out():
    ev = [("%while.1 = (s32[]) while((s32[]) %a), body=%b", 0.0, 10.0),
          ("%fusion.2 = f32[8] fusion(f32[8] %x), kind=kLoop", 1.0, 4.0),
          ("%copy-done.3 = f32[8] copy-done((f32[8]) %c)", 4.0, 9.0),
          ("%fusion.7.remat = f32[8] fusion(f32[8] %y)", 12.0, 13.0)]
    own = {tr.op_name(t): o for t, _, _, o in tr.self_times(ev)}
    assert own == {"while": 2.0, "fusion": 3.0, "copy-done": 5.0,
                   "fusion.remat": 1.0}
    assert [tr.op_kind(e[0]) for e in ev] == ["while", "fusion",
                                              "copy-done", "fusion"]


def _synthetic():
    kernel = ("%call.1 = bf16[4,16,1,128] custom-call(%q, %k), "
              "custom_call_target=\"tpu_custom_call\", operand_layout="
              "{bf16[49,128,2048]{2,1,0}}")
    flash = ("%attn.9 = (bf16[1,16,256,128], f32[1,16,256]) custom-call("
             "%q, %k, %v), custom_call_target=\"tpu_custom_call\"")
    ops = [("%fusion.1 = f32[8] fusion(%a)", 1.0, 2.0), (kernel, 2.0, 2.5),
           ("%all-gather-done.4 = f32[8] all-gather-done(%s)", 2.5, 3.0),
           ("%copy-done.5 = f32[8] copy-done(%c)", 3.0, 4.0),
           ("%async-collective-done.2 = f32[8] async-done(%s)", 4.0, 4.25),
           (flash, 6.0, 6.5), ("%fusion.8 = f32[8] fusion(%a)", 6.5, 7.0)]
    mods = [("jit__unknown(1)", 1.0, 4.0), ("jit__unknown(2)", 6.0, 7.0)]
    spans = [("bench:window", 0.0, 10.0), ("bench:step", 0.5, 4.2),
             ("bench:stamp", 4.2, 5.0), ("bench:step", 5.0, 7.5)]
    return tr.Reduced({0: {"ops": ops, "modules": mods}}, spans)


def test_reduction_of_a_hand_made_trace():
    red = _synthetic()
    assert red.window_s == 10.0 and red.busy_s == 4.25
    assert abs(red.idle_pct() - 57.5) < 1e-9
    assert red.exposed_pct(tr.COPY_OPS) == 10.0
    assert red.exposed_pct(tr.COLLECTIVES) == 7.5   # 0.5 + 0.25 s
    ops = dict(red.device_ops())
    assert ops["fusion"] == 1.5 and ops["copy-done"] == 1.0
    gaps = dict(red.idle_gaps())
    # by the middle of each gap: 0-1 -> the first step (0.5-4.2),
    # 4.25-6 -> the second step (its middle, 5.125, is past the stamp
    # span's end), 7-10 -> no span at 8.5
    assert gaps == {"bench:step": 2.75, "_no_host_span_": 3.0}
    # kernels are Pallas calls, whatever encloses or names them
    assert [tr.op_name(t) for t, _, _ in red.devices[0].kernels()] == [
        "call", "attn"]


def test_recorded_tpu_trace_reduces():
    """A trace recorded on a TPU v5 lite (four runs of a 1024^3 matmul +
    tanh + sum, ``bench:step`` / ``bench:stamp`` spans around them)."""
    red = tr.read(os.path.join(BENCH, "testdata",
                               "tiny_tpu_trace.xplane.pb"))
    assert len(red.devices) == 1
    dev = red.devices[0]
    # with no bench:window span the window is the ops' own extent, and
    # only program executions wholly inside it count: the first and the
    # last of the four start a little before their first instruction
    assert len(dev.modules) == 2
    assert all(n.startswith("jit__lambda(") for n, _, _ in dev.modules)
    ops = dict(red.device_ops())
    assert set(ops) == {"fusion", "copy-start", "copy-done"}
    # 4 fusions of ~11.9 us each are all the busy time
    assert abs(ops["fusion"] - 4 * 11.9e-6) < 1e-6
    assert abs(red.busy_s - sum(ops.values())) < 1e-9
    assert 99.0 < red.idle_pct() < 100.0
    assert {n for n, _, _ in red.host_spans} == {"bench:step",
                                                 "bench:stamp"}
    assert not dev.kernels()


# --------------------------------------------- the harness, end to end

def _tmp_benchmark(tmp_path, extra_metric=None):
    """A benchmark directory made of the real readers and model families
    and the tiny configurations and mixes, plus a contract over it."""
    root = tmp_path / "bench"
    for d in ("metrics", "models"):
        shutil.copytree(os.path.join(BENCH, d), root / d)
    shutil.copytree(os.path.join(BENCH, "testdata", "configs"),
                    root / "configs")
    shutil.copytree(os.path.join(BENCH, "testdata", "traffic"),
                    root / "traffic")
    contract = json.loads(json.dumps(CONTRACT))
    contract["configs"] = [
        {"name": n, "file": f"bench/configs/{n}.json"}
        for n in sorted(set(TWIN_CONFIGS.values()))]
    contract["workloads"] = [
        {"name": w, "config": c, "traffic": t, "chips": k}
        for w, (c, t, k) in TWINS.items()]
    return root, contract


def _run_cell(contract, repo, workload, seconds=1.0, seed=3):
    import jax
    cell = harness.resolve_cell(contract, workload, repo=str(repo))
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    run, _ = harness.run_cell(cell, args, time.time(),
                              jax.devices()[:cell["cell"]["chips"]],
                              "TPU v5 lite")
    metrics = harness.read_metrics(
        cell["end_to_end"] + cell["per_layer"], run, None,
        harness.units_of(contract), cell["root"])
    return cell, run, metrics


@pytest.mark.parametrize("workload", CELLS)
def test_each_cells_control_flow_at_a_tiny_size(tmp_path, workload):
    assert all(TWINS[workload]), (
        f"{workload} has no tiny twin: add testdata/configs and "
        "testdata/traffic files whose twin_of names its configuration "
        "and its traffic")
    _, contract = _tmp_benchmark(tmp_path)
    cell, run, metrics = _run_cell(contract, tmp_path, workload)
    assert all(run["checks"].values()), run["checks"]
    assert run["failed"] == 0 and run["attempted"] > 0
    assert run["compiles_in_window"] == 0
    # with no trace the device-trace metrics are left out, the others read
    assert set(cell["end_to_end"]) <= set(metrics)
    assert metrics["setup_s"]["value"] > 0
    if run["kind"] == "train":
        # whole steps only, between two boundaries
        assert run["steps"] == len(run["step_seconds"])
        assert abs(sum(run["step_seconds"]) - run["window_s"]) < 0.05
        assert run["window_s"] >= 1.0
        assert run["loss_error"] < 1e-3
    else:
        assert run["reference_check"]["ok"]
        got = sum(len(r.token_times) for r in run["counted"])
        if run["traffic_kind"] == "open_loop":
            # every request due in the window was drained and counts
            assert got == run["totals"]["output_tokens"]
            assert len(run["counted"]) == run["totals"]["requests"]
            assert all(r.token_times[0] >= r.due for r in run["counted"])
        else:
            # tokens inside the window, whether or not the request ended
            assert 0 < run["window_tokens"] < got
            assert run["steps"][run["first_step"] - 1][2] == run["num_slots"]


@pytest.mark.parametrize("workload", ["serve-gpt2-1.3b-batch",
                                      "train-gpt2-1.3b-offload"])
def test_a_broken_timed_path_comes_out_not_correct(tmp_path, workload,
                                                   monkeypatch):
    """The rest of a run with the timed path broken underneath: a served
    token altered where the server hands it out; a step whose loss is
    not the batch's."""
    _, contract = _tmp_benchmark(tmp_path)
    if workload.startswith("serve"):
        from deepspeed_tpu.inference import ContinuousBatchingServer
        result = ContinuousBatchingServer.result

        def altered(self, rid):
            tokens = list(result(self, rid))
            tokens[-1] = (tokens[-1] + 1) % 64
            return tokens
        monkeypatch.setattr(ContinuousBatchingServer, "result", altered)
        broken = "matches_reference"
    else:
        import deepspeed_tpu
        initialize = deepspeed_tpu.initialize

        def wrapped(*a, **k):
            got = initialize(*a, **k)
            step = got[0].train_batch
            got[0].train_batch = lambda batch: dict(
                step(batch), loss=step(batch)["loss"] + 0.05)
            return got
        monkeypatch.setattr(deepspeed_tpu, "initialize", wrapped)
        broken = "first_loss_matches_reference"
    cell, run, _ = _run_cell(contract, tmp_path, workload)
    assert run["checks"][broken] is False
    assert not all(run["checks"].values())          # ``correct``: false
    number, limit = run["compared"][
        "max_gap" if workload.startswith("serve") else "first_loss_error"]
    assert number > limit
    line = json.loads(harness.result_line(
        all(run["checks"].values()), run["attempted"], run["failed"], {},
        {}, compared=run["compared"]))
    assert line["correct"] is False and list(line)[-1] == "compared"


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A configuration, a mix and a per-layer metric that no file of the
    benchmark knows, added as new files and entries, run as they are."""
    root, contract = _tmp_benchmark(tmp_path)
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(root) for p in fs}
    cfg = harness.load_json(root / "configs" / "tiny-serve.json")
    cfg["engine"]["num_slots"] = 2
    (root / "configs" / "dummy.json").write_text(json.dumps(cfg))
    mix = harness.load_json(root / "traffic" / "tiny-chat.json")
    mix.update(arrivals={"process": "gamma", "cv": 2.0}, rate_per_s=8)
    (root / "traffic" / "burst.json").write_text(json.dumps(mix))
    (root / "metrics" / "dummy_steps.py").write_text(
        "def read(run, trace):\n    return len(run['steps'])\n")
    contract["configs"].append({"name": "dummy",
                                "file": "bench/configs/dummy.json"})
    contract["workloads"].append({"name": "dummy.burst", "config": "dummy",
                                  "traffic": "burst", "chips": 1})
    contract["end_to_end"][2].setdefault("workloads", []).append(
        "dummy.burst")
    contract["per_layer"].append(
        {"name": "dummy_steps", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "server host loop",
         "moves": "ttft_p90_ms", "workloads": ["dummy.burst"]})
    cell, run, metrics = _run_cell(contract, tmp_path, "dummy.burst")
    assert all(run["checks"].values())
    assert metrics["dummy_steps"]["value"] == len(run["steps"]) > 0
    assert run["num_slots"] == 2
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(root) for p in fs}
    assert all(after[p] == t for p, t in before.items())


def test_without_a_tpu_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert p.returncode != 0
    assert "no CPU fallback" in p.stderr
    assert "metrics" not in p.stdout and "correct" not in p.stdout
    assert "--cpu" not in open(os.path.join(BENCH, "run.py")).read()


def test_rotation_keeps_every_neighbour_and_gap():
    """``order: rotation``: every seed offers the same ring of requests
    at the same gaps, begun at another place."""
    tr_ = _traffic("chat-p80")
    assert tr_["order"] == "rotation"

    def ring(seed):
        reqs = [r for r in T.build_requests(tr_, 50, seed, 50257)["requests"]
                if r["counted"]]
        due = np.array([r["due"] for r in reqs])
        gaps = np.append(np.diff(due), 50 - (due[-1] - due[0]))
        return [(len(r["prompt"]), r["out"]) for r in reqs], gaps, due
    a, ga, da = ring(1)
    b, gb, db = ring(2 ** 31 + 13)
    assert a != b and sorted(a) == sorted(b)
    k = next(k for k in range(len(a)) if b[k:] + b[:k] == a)
    assert np.allclose(np.roll(gb, -k), ga)
    assert 0 <= da[0] and da[-1] < 50 and 0 <= db[0] and db[-1] < 50
    assert abs(np.std(ga) / np.mean(ga) - 1.0) < 0.05   # exponential gaps


def test_reference_matches_both_parameter_trees_loss_and_gradient():
    """At a small size on the CPU: the plain reference against the
    training model (loss AND gradient, which at 1.3B do not fit on the
    chip beside anything) and against the serving model (logits)."""
    import jax
    import jax.numpy as jnp

    model = harness.load_json(os.path.join(
        BENCH, "testdata", "configs", "tiny-train.json"))["model"]
    fam = harness.load_family("gpt2", BENCH)
    tm = fam.train_model(model)
    params = fam.train_params(tm, 3)
    ids = np.random.default_rng(0).integers(0, model["vocab_size"],
                                            size=(4, 64))
    batch = {"input_ids": jnp.asarray(ids)}

    def ref_loss(p):
        total, count = fam.reference.nll(fam.reference_from_train(tm, p),
                                         ids, model["vocab_size"])
        return total / count
    want, want_g = jax.value_and_grad(ref_loss)(params)
    got, got_g = jax.value_and_grad(tm.loss_fn)(params, batch)
    # float32 on both sides: only the order of additions differs
    assert abs(float(want) - float(got)) < 1e-5
    for a, b in zip(jax.tree.leaves(want_g), jax.tree.leaves(got_g)):
        assert np.allclose(a, b, atol=2e-5, rtol=1e-3)
    assert abs(fam.reference.loss(fam.reference_from_train(tm, params),
                                  ids, model["vocab_size"]) - float(got)
               ) < 1e-5

    from deepspeed_tpu.model_implementations.transformer import (
        causal_forward)
    cfg, sp = fam.serve_model(model, 3)
    want = fam.reference.logits(fam.reference_from_serve(cfg, sp),
                                ids[:2, :32])
    got = causal_forward(sp, cfg, jnp.asarray(ids[:2, :32]))
    assert np.abs(np.asarray(want) - np.asarray(got)).max() < 1e-4
