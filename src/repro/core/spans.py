"""Host-time spans of a batched replay, on the JAX profiler's clock.

Every span is a :class:`jax.profiler.TraceAnnotation` named
``mind.<name>``: while a ``jax.profiler`` trace records, the profiler
stamps it on the same clock as the device's executables; otherwise it is
one inert object.  The scalar emulator never loads JAX, and a process
without ``jax.profiler`` loaded has no trace to record into, so there a
span is a null context and this module imports nothing heavy.
docs/OBSERVABILITY.md lists the names.
"""

from __future__ import annotations

import contextlib
import functools
import sys


def span(name: str, **meta):
    """The context of span ``mind.<name>`` with ``meta`` as its metadata."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return contextlib.nullcontext()
    return prof.TraceAnnotation(f"mind.{name}", **meta)


def spanned(name: str):
    """Decorator: every call of the function runs in span ``mind.<name>``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
