"""Mean |predicted NNZ(C) - NNZ(C)| / NNZ(C) over the window's answered
requests, in percent: the paper's accuracy metric, read from each plan."""
import numpy as np


def read(run):
    errs = [abs(s.predicted_nnz - run.nnz_c[s.key]) / run.nnz_c[s.key]
            for s in run.answered if run.nnz_c[s.key]]
    return 100.0 * float(np.mean(errs)) if errs else None
