"""Device time per execution of the jitted megastep program
(``core/megastep.py`` ``_megastep``), in ms, from the trace's program
events."""
MODULE = r"_megastep"


def read(run):
    if run.trace is None:
        return None
    ns, n = run.trace.module_ns(MODULE)
    return ns / n / 1e6 if n else None
