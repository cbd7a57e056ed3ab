"""Named device scopes and host spans of the query path.

``scope(name)`` is a :func:`jax.named_scope`: it names the XLA ops traced
under it (their ``op_name`` metadata), so a profiler trace can split one
compiled program by stage.  It costs nothing at run time.  Use it inside
the traced function, as a ``with`` block or as a decorator under
``jax.jit``: a scope around a call into a separately jitted function does
not reach that function's ops.

``span(name)`` is a :class:`jax.profiler.TraceAnnotation`: a host-side
interval written into the profiler's trace on the device ops' clock.
With no profiler running it costs about a microsecond.  ``sync.*`` spans
each wrap one blocking device-to-host read.

Every name either emits is listed in :data:`SCOPES` or :data:`SPANS`;
``docs/performance.md`` says what each covers.
"""

from __future__ import annotations

import jax

# device scopes (op_name metadata inside the compiled programs)
HOTSET = "veilgraph.hotset"
HOTSET_KR = "veilgraph.hotset.kr"
HOTSET_KN = "veilgraph.hotset.kn"
HOTSET_KDELTA = "veilgraph.hotset.kdelta"
SUMMARY = "veilgraph.summary"
SUMMARY_SELECT = "veilgraph.summary.select"
SUMMARY_BIN = "veilgraph.summary.bin"
SUMMARY_ORDER = "veilgraph.summary.order"
SWEEP = "veilgraph.sweep"
DRIFT = "veilgraph.drift"
PUSH = "veilgraph.push"
LAYOUT = "veilgraph.layout"
EXACT = "veilgraph.exact"

SCOPES = (HOTSET, HOTSET_KR, HOTSET_KN, HOTSET_KDELTA, SUMMARY,
          SUMMARY_SELECT, SUMMARY_BIN, SUMMARY_ORDER, SWEEP, DRIFT, PUSH,
          LAYOUT, EXACT)

# host spans (``LAYOUT`` also names the host span of the layout rebuild)
QUERY = "veilgraph.query"
WAVE = "veilgraph.wave"
APPLY = "veilgraph.apply"
STEP = "veilgraph.step"
SYNC_VIEW = "veilgraph.sync.view"
SYNC_COUNTS = "veilgraph.sync.counts"
SYNC_FALLBACK = "veilgraph.sync.fallback"
SYNC_READY = "veilgraph.sync.ready"
SYNC_STATS = "veilgraph.sync.stats"
SYNC_ITERS = "veilgraph.sync.iters"
SYNC_REBALANCE = "veilgraph.sync.rebalance"
SYNC_FETCH = "veilgraph.sync.fetch"

SPANS = (QUERY, WAVE, APPLY, LAYOUT, STEP, SYNC_VIEW, SYNC_COUNTS,
         SYNC_FALLBACK, SYNC_READY, SYNC_STATS, SYNC_ITERS, SYNC_REBALANCE,
         SYNC_FETCH)


def scope(name: str):
    """Name the device ops traced under it (a :func:`jax.named_scope`)."""
    return jax.named_scope(name)


def span(name: str):
    """A host interval on the profiler's clock (a ``TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)
