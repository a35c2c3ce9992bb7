"""Rows per engine dispatch: ``SchedulerStats.rows_completed /
n_dispatches`` of the window's scheduler."""


def read(run):
    s = run.record.sched
    if not s or not s["n_dispatches"]:
        return None
    return s["rows_completed"] / s["n_dispatches"]
