"""Share of the traced window in which no op ran on the device, in %:
1 - (union of the device's op intervals) / window (open loops)."""


def read(run):
    if run.trace is None or not run.record.tickets:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns() / run.trace.window_ns)
