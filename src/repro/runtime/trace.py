"""Spans and counters of the federation.

``span(name, **args)`` marks one step of the federation as a
``jax.profiler.TraceAnnotation``, so it lands in the profiler's trace on
the same clock as the device's operations.  A span is recorded exactly
when a profiler session is running (``jax.profiler.start_trace`` or a
connected ``start_server``); otherwise ``span`` returns a shared no-op
context and costs about half a microsecond.

``count(name, n)`` adds to a process-wide tally of plain ints that is
always on; ``counters()`` returns a copy.  Read a stretch of a run as the
difference of two snapshots.  The tallies:

* ``host_syncs``: blocking device-to-host reads and waits on the
  federation's path (each ``fl.sync`` site, and each evaluation);
* ``h2d_bytes``: bytes of host arrays copied to the device on that path
  (training data, merge weights, server-optimizer scalars).  A worker
  keeps its shard on the device, so its bytes count once, at its first
  training;
* ``shard_uploads``: trainings that copied the worker's shard to the
  device (its first);
* ``shard_hits``: trainings served from the shard already there;
* ``train_tokens``: tokens a language model's local trainings processed
  (the shard's tokens times its epochs, read from the shape).
"""
from __future__ import annotations

import contextlib
from typing import Dict

import jax
import jax.numpy as jnp

_enabled = jax.profiler.TraceAnnotation.is_enabled
_annotation = jax.profiler.TraceAnnotation
_OFF = contextlib.nullcontext()
_counts: Dict[str, int] = {}


def span(name: str, **args):
    """A context that records ``name`` (with ``args``) while the profiler
    traces, and does nothing otherwise."""
    if _enabled():
        return _annotation(name, **args)
    return _OFF


def count(name: str, n: int = 1) -> None:
    _counts[name] = _counts.get(name, 0) + n


def counters() -> Dict[str, int]:
    """A snapshot of every tally."""
    return dict(_counts)


def to_device(x, dtype=None):
    """``jnp.asarray(x, dtype)``, counting the bytes copied under
    ``h2d_bytes`` when ``x`` is a host value."""
    out = jnp.asarray(x, dtype)
    if not isinstance(x, jax.Array):
        count("h2d_bytes", out.nbytes)
    return out
