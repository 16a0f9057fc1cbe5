"""Kernel profiling hooks: host-side trace annotations + HLO name scopes.

Two complementary mechanisms, matching how JAX profiling actually works:

  * ``annotate(name)`` -- a host-side ``jax.profiler.TraceAnnotation``
    context.  Wrapped around *dispatch sites* (the router's graph/brute
    sub-batch calls, scan dispatch), it brackets the host span that enqueues
    and waits on device work, so a ``jax.profiler.trace`` capture attributes
    device time to routes and bucket shapes.  Runtime-gated: it is a
    ``nullcontext`` unless ``set_kernel_annotations(True)`` ran (the ``Obs``
    facade flips it when ``ObsSpec.kernel_annotations`` is set), so the
    steady-state cost of the hook is one global read.

  * ``jax.named_scope(name)`` -- used directly *inside* jitted kernel
    wrappers (``pq_adc``, ``filtered_topk``, ``gather_distance``) and the
    graph-traversal wave body.  It runs at trace time only, stamping the
    scope name into HLO op metadata; compiled executables carry it for free,
    so it needs no gating and never perturbs results.
"""
from __future__ import annotations

from contextlib import nullcontext

import jax

_KERNEL_ANNOTATIONS = False


def set_kernel_annotations(on: bool) -> None:
    """Globally enable/disable host-side dispatch annotations."""
    global _KERNEL_ANNOTATIONS
    _KERNEL_ANNOTATIONS = bool(on)


def kernel_annotations_enabled() -> bool:
    return _KERNEL_ANNOTATIONS


def annotate(name: str):
    """A TraceAnnotation context for ``name`` (nullcontext when disabled)."""
    if not _KERNEL_ANNOTATIONS:
        return nullcontext()
    return jax.profiler.TraceAnnotation(name)
