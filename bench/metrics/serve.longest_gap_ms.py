"""The longest stretch, in ms, between two consecutive completions of
requests (``Ticket.completed_at``), over every completed request of the
window. At the cells' rates requests are due every few ms, so a stretch
far over one batch's service time is the serving process on hold: a
collector pause, a lock, a stall of the host."""
import numpy as np


def read(run):
    done = sorted(t["completed_at"] for t in run.record.tickets
                  if t["status"] == "done")
    if len(done) < 2:
        return None
    return 1e3 * float(np.max(np.diff(done)))
