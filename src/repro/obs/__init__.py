"""Observability for the serving stack — the flight recorder.

Three pieces, stdlib-only (importable before jax, safe from any thread):

* :mod:`repro.obs.trace` — ring-buffer span tracer, off by default,
  one ``None``-check when disabled. Production code brackets stages
  with ``trace.span(...)`` / stamps instants with ``trace.event(...)``;
  ``obs.capture()`` scopes a recording. ``Tracer(profiler=True)``
  also writes every span into a running JAX profiler trace, beside
  the device's ops.
* :mod:`repro.obs.metrics` — always-on counters / gauges / fixed-bucket
  histograms (p50/p99/p999 without stored samples) published into the
  process-global ``metrics.REGISTRY`` by the scheduler, the engines,
  shard health, the mutable index, and fault injection.
* :mod:`repro.obs.export` — JSONL span dump, Chrome trace-event JSON
  (Perfetto-loadable), Prometheus text rendering, and the per-query
  ``explain(ticket)`` span-tree reconstruction.

The hard invariant the instrumentation honors everywhere: **zero
steady-state host syncs**. Span timings come from wall-clock brackets
around boundaries that already synchronize (dispatch host work, the
finalize fetch); span attributes carry only host-side values (sizes,
config knobs, per-attempt ``JoinStats`` fields) — never a ``jax.Array``
a recorder would have to fetch. The CI bench guard pins this with the
``traced_steady_state_syncs`` hard-zero row next to the untraced one.
"""
from . import export, metrics, trace
from .export import (chrome_trace, explain, format_explain,
                     render_prometheus, spans_to_jsonl, write_chrome_trace,
                     write_jsonl)
from .metrics import Registry
from .trace import Tracer, capture, enabled, event, install, span, uninstall

# the live default registry is ``metrics.REGISTRY`` — accessed through
# the module on purpose, so ``metrics.scoped()`` (tests/benches) can
# swap it; a frozen re-export here would silently go stale
__all__ = [
    "Registry", "Tracer", "capture", "chrome_trace",
    "enabled", "event", "explain", "export", "format_explain", "install",
    "metrics", "render_prometheus", "span", "spans_to_jsonl", "trace",
    "uninstall", "write_chrome_trace", "write_jsonl",
]
