"""Host spans of a fit's phases, written into the profiler's own trace.

`span(name, **args)` is a `jax.profiler.TraceAnnotation` named
`repro:<name>` that also carries the current fit's number as `fit`, so
every span of one `api.fit` shares one identifier.  Under
`jax.profiler.trace` the spans land on the host plane, on the device
planes' clock, and each stretch of device idle time can be put down to the
phase the host was in.  Outside a profiler session a span records nothing
and costs only the context manager.

Host spans: `repro:fit` (Protocol.fit), `repro:setup` (Copml.setup, with
`m`, `d`, `n`: host preparation and the dispatch of the compiled setup
program), `repro:loop` (the jit engine's compiled loop, with `iters`);
`repro:finish` (scoring and the TrainResult).  The compiled programs name
their device work with `jax.named_scope` instead, which lands in the ops'
`op_name` metadata: setup's `copml.setup.share`, `copml.setup.encode`,
`copml.setup.xty`, and the loop's `copml.encode_model`, `copml.step_rand`,
`copml.fused_step`.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools

import jax

_fit_id = contextvars.ContextVar("repro_fit_id", default=0)
_fit_ids = itertools.count(1)


def span(name: str, **args):
    """A `repro:<name>` host span of the current fit (fit 0 outside one)."""
    return jax.profiler.TraceAnnotation(f"repro:{name}", fit=_fit_id.get(),
                                        **args)


@contextlib.contextmanager
def fit():
    """Number a new fit and hold its `repro:fit` span open."""
    token = _fit_id.set(next(_fit_ids))
    try:
        with span("fit"):
            yield
    finally:
        _fit_id.reset(token)
