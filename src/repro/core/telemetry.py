"""Names the engine gives its work in a profiler trace.

Host phases are ``blade.<name>`` spans: ``jax.profiler.TraceAnnotation``
events on the profiler's clock, the one the device ops are stamped with.
Counts ride on a span as event arguments (``span("dispatch", rounds=5)``
lands in the trace as the stat ``rounds = 5``), so they are scoped to the
traced window and need no counter table of their own. With no profiler
session active a span is a no-op trace event: the session is the only
switch. Pass only counts already at hand (ints, ``len``, ``.nbytes``).

Device work is named by stage: each function the ``make_*`` stage
factories of ``core/rounds.py`` return runs under ``jax.named_scope`` of
its stage, so every op it lowers to carries the stage in its ``op_name``
metadata, whichever driver composes the stages (scan, loop, cohort,
sharded). The scope is metadata only: the ops and their results are the
same with or without it.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "blade."

# the stage scopes, in round order
STAGES = ("local_train", "perturb", "attack", "communicate", "mine",
          "finalize")


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """The host span ``blade.<name>``, carrying ``counts`` as its args."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)


def stage(name: str):
    """Decorator: trace the stage function under ``jax.named_scope(name)``."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {STAGES}")

    def scoped(fn):
        @functools.wraps(fn)
        def in_scope(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return in_scope

    return scoped


def nbytes(tree) -> int:
    """Bytes held by the arrays of ``tree`` (shapes only, no transfer)."""
    return sum(x.nbytes for x in jax.tree.leaves(tree))
