"""What the planner's programs and the online loop show of themselves.

Compiles. Every program the engine, the online loop and the split server
jit is wrapped by :func:`recorded`, which appends the program's kind to
every active :func:`compile_log` at each trace (jax.jit re-runs the python
body only when its signature cache misses, so a trace is a compilation)
and names the program after its kind: it lowers as XLA module
``jit_<kind>``, which is how a device trace tells the programs apart.

Spans. :func:`span` is a host span on the profiler's timeline
(``jax.profiler.TraceAnnotation``). There is nothing to switch on: with no
profiler session active a span records nothing.

Host reads. :func:`host_read` is the one way the online path turns a
device value into a host value. It reads under the span ``sync.<name>``
and counts the read per name, so a trace shows where the host waited on
the device and an operator sees how many reads an epoch makes.

Device scopes (``jax.named_scope``) sit where the work is: the solver
phases in ``core/li_gd.py`` (``gd_iter``, ``warm_gate``,
``greedy_rounding``) and each NOMA kernel call in
``kernels/noma_rates.py`` (``noma_<kernel>_<link>_<pass>``). They change
op names and metadata only, never the compiled program.
"""
from __future__ import annotations

import collections
import contextlib
import functools

import jax

_COMPILE_LOGS: list[list[str]] = []


@contextlib.contextmanager
def compile_log():
    """Record the kind of every recorded program traced inside the block:

        with compile_log() as log:
            eng.plan(env); eng.replan(state, env)
        assert log == ["plan", "replan"]

    Entries appear at trace time, so a steady-state loop that appends
    nothing proves zero recompiles. Nesting is fine (each context gets its
    own list); tracing-only inspection (engine.program + jax.make_jaxpr /
    jax.eval_shape) also records, so keep audit traffic outside the block
    when counting execution compiles."""
    sink: list[str] = []
    _COMPILE_LOGS.append(sink)
    try:
        yield sink
    finally:
        _COMPILE_LOGS.remove(sink)


def recorded(fn, kind: str, name: str | None = None):
    """``fn``, to be jitted, logging ``kind`` at each trace and named
    ``name`` (default ``kind``): ``jax.jit`` lowers it as module
    ``jit_<name>``. Its signature (the parameter names) stays ``fn``'s."""
    @functools.wraps(fn)
    def program(*args):
        for sink in _COMPILE_LOGS:
            sink.append(kind)
        return fn(*args)
    program.__name__ = program.__qualname__ = name or kind
    return program


def span(name: str):
    """A host span named ``name`` on the profiler's timeline."""
    return jax.profiler.TraceAnnotation(name)


def host_read(x, name: str, counts: collections.Counter):
    """``x`` (a device array or pytree) as host values, read under the span
    ``sync.<name>`` and counted in ``counts[name]``."""
    with span(f"sync.{name}"):
        value = jax.device_get(x)
    counts[name] += 1
    return value

