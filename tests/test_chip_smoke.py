"""``chip_smoke.py`` — what can be said of it without a chip.

On the CPU the script must fail fast and say so (there is no fallback),
the compile-cache helper must obey the environment, and — the first
rehearsal of the ``on-chip-measurement`` guide — its phases, imported as
functions, must run end to end at a tiny width. That last test is
``slow``: the builder runs it before every chip call, tier-1 does not.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)


def test_fails_fast_without_a_tpu():
    """No accelerator: non-zero exit within seconds (the device check
    runs before any heavy import), last line ``"ok": false``."""
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=60,
                       cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "deepspeed_tpu" not in p.stderr     # package never imported


def test_compile_cache_dir_obeys_the_environment(monkeypatch):
    """Placed from outside when JAX_COMPILATION_CACHE_DIR is set, else
    one fixed in-checkout path — asserted on the pure helper, without
    initialising a cache (the suite keeps the cache off)."""
    from deepspeed_tpu.utils import compile_cache as cc
    monkeypatch.delenv(cc.ENV, raising=False)
    assert cc.compile_cache_dir() == os.path.join(ROOT, ".jax_compile_cache")
    assert cc.compile_cache_dir("/json/key") == "/json/key"
    monkeypatch.setenv(cc.ENV, "/placed/from/outside")
    assert cc.compile_cache_dir() == "/placed/from/outside"
    assert cc.compile_cache_dir("/json/key") == "/placed/from/outside"


@pytest.mark.slow
def test_phases_rehearsal_tiny_cpu():
    """The script's own phases at a tiny width on the CPU (kernels in
    interpret mode, host-path offload, virtual devices for --chips 4):
    wrong paths, arguments and control flow surface here, for no chip
    time. The chip-only proofs (compiled kernel text, pinned-host
    state) are switched off by ``on_chip=False`` — in the test, not by
    an option of the program."""
    import chip_smoke as cs
    tiny = cs.Size(n_embd=64, n_head=4, vocab=256, seq=256, train_layers=2,
                   serve_layers=2, sharded_layers=2, block_size=32,
                   num_slots=4, micro=2, train_steps=4, new_tokens=6,
                   chunk_tokens=64, dtype="float32", on_chip=False)
    k = cs.kernels_phase(tiny, seed=0)
    assert len(k["max_abs_err"]) == 7
    assert cs.host_ops_phase(seed=0)["cpu_adam_native_vs_numpy"] < 1e-5
    t = cs.train_phase(tiny, seed=0)
    assert t["losses"][-1] < t["losses"][0]
    s = cs.serve_phase(tiny, seed=0)
    assert s["default"]["decode_traces"] == 1
    assert s["chunked_speculative"]["verify_traces"] == 1
    sh = cs.sharded_phase(tiny, seed=0)
    assert len(sh["state_bytes_per_device"]) == 4
