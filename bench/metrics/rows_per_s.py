"""R rows answered per second: every row of every batch of the window,
over the time from the window's start to the last batch's completion
(host clock, closed loops)."""


def read(run):
    return run.record.values.get("rows_per_s")
