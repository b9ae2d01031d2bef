"""Host loop (launch/train.py::train_loop): the 95th percentile of every
step interval in the run's whole window, in ms: ``step_ms_p95`` where the
host's speed moves it too much from run to run to bound it end to end."""
import numpy as np


def read(r):
    return float(np.percentile(r.interval_ms, 95)) if r.interval_ms else None
