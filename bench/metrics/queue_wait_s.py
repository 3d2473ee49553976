"""Mean seconds from a request's due time to its EXECUTING state in the
service's own request history (generator lateness, queueing, admission and
planning: everything before its executor runs)."""
import numpy as np


def read(run):
    waits = [s.executing_at - s.due for s in run.answered]
    return float(np.mean(waits)) if waits else None
