"""Small jit-caching helpers.

Param init must run as ONE compiled executable: unjitted init compiles
and dispatches each RNG/initializer op individually — hundreds of tiny
programs and full-size fp32 temporaries for a 1.3B model, where the
jitted form is one program that casts as it goes. But ``jax.jit``'s
trace cache is keyed per wrapper object, so wrapping at every call would
re-trace and re-compile each time — the wrapper itself must be cached.
"""
from __future__ import annotations


def instance_cached_jit(obj, fn, key: str = "_jit_init",
                        name: str | None = None):
    """Return a jitted ``fn`` memoized in ``obj.__dict__[key]``.

    Repeated calls on the same instance reuse one traced executable.
    ``__dict__`` is used directly so the helper stays safe on classes
    with custom ``__getattr__``.

    The wrapper is a flight-recorder
    :class:`~deepspeed_tpu.telemetry.compile_watch.WatchedFunction`
    rather than a bare ``jax.jit``: an init that silently recompiles
    (new shape through the same instance) surfaces as a ``retrace``
    event with compile timing instead of an unexplained multi-minute
    stall. ``name`` labels it in ``compile_report()`` (default:
    ``<ClassName>.<key>``).

    Note: compile metrics record into the PROCESS registry — model
    init runs before any engine exists, so an engine-level
    ``telemetry.enabled=false`` (which scopes the engine's own
    recording to a private registry) cannot reach back here. The cost
    is bounded: a few ``jit_*`` series labeled by class name.
    """
    wrapper = obj.__dict__.get(key)
    if wrapper is None:
        from deepspeed_tpu.telemetry.compile_watch import watched_jit
        label = name or f"{type(obj).__name__}.{key.lstrip('_')}"
        wrapper = obj.__dict__[key] = watched_jit(fn, name=label)
    return wrapper
