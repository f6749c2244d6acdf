"""The driver-facing entry point must stay healthy: entry() lowers
under jit."""
import importlib.util
import os

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

pytestmark = pytest.mark.slow


def test_entry_lowers():
    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(ROOT, "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    lowered = jax.jit(fn).lower(*args)      # trace+lower only, no compile
    assert "hlo" in lowered.as_text()[:2000].lower() or \
        lowered.as_text()                    # non-empty HLO text

