"""Unified telemetry for the Julienning stack.

Three zero-dependency pieces (stdlib only — importable from `repro.core`
without dragging in jax):

- :mod:`repro.obs.metrics` — a process-global registry of named counters,
  gauges, and histograms with label support and one
  ``snapshot()``/``reset()``/``diff()`` API. The historical ad-hoc counter
  dicts (``TRACE_COUNT`` ×3, ``SOLVE_COUNT``, ``COMMIT_STATS``) are now
  registry-backed dict subclasses, so every existing snapshot-and-diff pin
  keeps working unchanged and one :func:`repro.obs.metrics.reset_all` zeroes
  everything.
- :mod:`repro.obs.trace` — a span tracer emitting Chrome ``trace_event``
  JSON loadable in Perfetto (https://ui.perfetto.dev). Spans carry wall-clock
  timestamps (the trace timeline) and, where the caller has one, the
  harness's virtual-clock time in ``args.vt``, an ``id`` and the ``parent``
  id; while enabled (after jax is imported), each span is also a
  ``jax.profiler`` annotation on the device trace's clock. Disabled by
  default; when disabled every ``span()`` returns a shared no-op context
  manager and hot paths guard on ``TRACER.enabled`` so tracing costs one
  attribute check.
- :mod:`repro.obs.ledger` — per-request / per-cycle attribution of tabulated
  energy draw into restore (E_s), compute, and NVM-commit categories, plus a
  replay-overhead category, with a conservation check against the
  ``HarvestModel`` pool delta at solver tolerance.
"""

from . import ledger, log, metrics, trace  # noqa: F401
from .ledger import EnergyLedger
from .metrics import METRICS, reset_all
from .trace import TRACER

__all__ = [
    "METRICS",
    "TRACER",
    "EnergyLedger",
    "ledger",
    "log",
    "metrics",
    "reset_all",
    "trace",
]
