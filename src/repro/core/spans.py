"""Named stages of the served path, on the profiler's clock.

``span(name, **args)`` marks one stage twice, with one name:

* on the host, a ``jax.profiler.TraceAnnotation``: an event on the
  calling thread, on the same clock as the device's ops, carrying
  ``args`` (a batch id or a size) so the spans of one batch can be
  matched;
* on the device, a ``jax.named_scope``: every op traced inside carries
  the name as a component of its ``op_name`` metadata, which the
  profiler reports beside the op.

Both halves cost next to nothing when no profiler runs: the annotation
is inactive, and the scope only changes trace-time metadata, never the
arithmetic.  Under ``jit`` the host half records trace time only.
Every name starts with ``sthc.``; ``docs/serving.md`` lists them.

Use it as a context manager around a stage, or as a decorator of a
stage function.
"""

from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def span(name: str, **args):
    with jax.profiler.TraceAnnotation(name, **args), jax.named_scope(name):
        yield
