"""95th percentile of every query's latency in the window, submit to the
answer ready on the device (linear interpolation between ranks)."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 95)) * 1e3
