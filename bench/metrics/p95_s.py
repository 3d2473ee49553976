"""95th percentile of the window's requests' latency, each timed from the
moment it was due to the moment its host CSR result was ready."""
import numpy as np


def read(run):
    if run.loop != "open" or not run.answered:
        return None
    return float(np.percentile(
        [s.finished_at - s.due for s in run.answered], 95))
