"""Host time an engine call adds, in ms: per call bracketed by the
benchmark's ``bench.engine_call`` span, its wall time minus the device's
busy time inside it; the median over calls (profiler trace, open
loops)."""
from bench import trace


def read(run):
    if run.trace is None or run.record.tickets == []:
        return None
    return trace.exposed_ms(run.trace)
