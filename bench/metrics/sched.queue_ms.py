"""Median over completed requests of the scheduler's dispatch instant
(``Ticket.dispatched_at``) minus the request's due time, in ms."""
import numpy as np


def read(run):
    waits = [1e3 * (t["dispatched_at"] - t["due"])
             for t in run.record.tickets
             if t["status"] == "done" and t["dispatched_at"] is not None]
    return float(np.median(waits)) if waits else None
