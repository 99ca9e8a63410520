"""Host spans the benchmark puts around its calls into each layer.

Each span is a ``jax.profiler.TraceAnnotation`` named ``bench.<layer>``, so
it lands in the profiler's trace on the same clock as the device ops, and
is also kept in memory with its host-clock start and end. Spans are on
only in a traced run; otherwise ``span`` is a no-op, so the untimed
bookkeeping never enters a measured window.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.on = False
        self.records = []   # (name, start_s, end_s) on the host clock

    @contextlib.contextmanager
    def span(self, name):
        if not self.on:
            yield
            return
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def wrap(self, name, fn):
        """``fn`` with a span around each call."""
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def seconds(self, name):
        """Host seconds covered by spans of ``name`` (nested spans of one
        name counted once)."""
        ivs = sorted((a, b) for n, a, b in self.records if n == name)
        total, end = 0.0, float("-inf")
        for a, b in ivs:
            if b > end:
                total += b - max(a, end)
                end = b
        return total


@contextlib.contextmanager
def patched(obj, attr, value):
    """Set ``obj.attr`` for the duration of the block."""
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, old)
