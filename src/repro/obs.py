"""Names for the profiler: device scopes and host spans.

Every name the program puts into a profiler trace starts with ``PREFIX``,
so a trace's own tools (or a reduction script) can pick them out without a
list:

* :func:`scope` (``jax.named_scope``) labels device work at a layer
  boundary.  It only writes ``op_name`` metadata into the compiled program
  (``repro.attention``, ``repro.mlp``, ``repro.kv_write``,
  ``repro.qsdp_gather``, ``repro.qsdp_reduce_scatter``); it changes no
  operation, fusion or layout, and costs nothing at run time.  Ops of a
  backward pass carry JAX's transform wrappers (``transpose(jvp(...))``,
  ``checkpoint``) around the same name, and the innermost scope names an
  op.  :func:`scoped` is the decorator form, for a function that owns one
  layer's work.
* :func:`program` names a jitted program: its compiled module, and its
  events on a trace's ``XLA Modules`` line, read ``jit_<name>``
  (``train_step``, ``serve_decode``, ``serve_prefill_chunk``,
  ``serve_verify``, ``serve_prefill``, and ``serve_encode_weights``, which
  runs once when a scheduler is built).
* :func:`span` (``jax.profiler.TraceAnnotation``) labels host work: the
  scheduler's admission, launches, syncs and token bookkeeping
  (``repro.sched.*``).  With no profiler running it costs about a
  microsecond.

Nothing is logged or written anywhere else: the names live in the
profiler's trace only (``jax.profiler.trace(dir)`` around a run; see the
README's "Reading a trace").
"""
from __future__ import annotations

import functools

import jax

PREFIX = "repro."


def scope(name: str):
    """Device scope ``repro.<name>`` over the ops traced inside it."""
    return jax.named_scope(PREFIX + name)


def scoped(name: str):
    """Decorator: run the function inside :func:`scope` ``(name)``."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco


def program(name: str, fn):
    """`fn` under the name `name`, to hand to ``jax.jit``; calls pass
    through unchanged."""
    def call(*args, **kwargs):
        return fn(*args, **kwargs)
    call.__name__ = call.__qualname__ = name
    return call


def span(name: str):
    """Host span ``repro.<name>`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(PREFIX + name)
