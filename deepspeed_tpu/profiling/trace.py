"""Profiler traces + named ranges.

Analog of the reference's NVTX instrumentation + nsight workflow
(``deepspeed/utils/nvtx.py`` ``instrument_w_nvtx``; SURVEY §5.1 maps it
to "jax profiler traces + xplane, per-phase named scopes"):

* ``instrument``: decorator wrapping a function in ``jax.named_scope``
  (shows up in xplane/Perfetto exactly where nvtx ranges show in
  nsight) plus an optional ``jax.profiler.TraceAnnotation`` for
  host-side spans.
* ``trace(logdir)``: context manager around
  ``jax.profiler.start_trace/stop_trace`` — the ``nsys profile``
  one-liner equivalent; view with TensorBoard's profile plugin or
  Perfetto.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional

import jax


def instrument(fn: Optional[Callable] = None, *, name: Optional[str] = None):
    """``@instrument`` or ``@instrument(name="phase")`` — the
    ``instrument_w_nvtx`` analog."""
    def deco(f):
        scope = name or getattr(f, "__qualname__", f.__name__)

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            with jax.named_scope(scope), \
                    jax.profiler.TraceAnnotation(scope):
                return f(*args, **kwargs)
        return wrapper

    return deco(fn) if fn is not None else deco


def scoped(name: str):
    """``@scoped("mlp")``: trace the function under
    ``jax.named_scope(name)`` and nothing else. The scope is metadata on
    the compiled instructions (``op_name="jit(f)/mlp/dot_general"``),
    which the compile watch's scope table turns into device time per
    layer (docs/observability.md "Spans"); the program's arithmetic,
    shardings and donation do not change."""
    def deco(f):
        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)
        return wrapper
    return deco


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_link: bool = False):
    """Capture an xplane trace for everything inside the block."""
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str):
    """Host+device range annotation (``with annotate("fwd"): ...``)."""
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield
