"""95th percentile latency (ms) of every query of the window, from the
moment it was due to its exact answer on the host (numpy's linear
interpolation)."""
import numpy as np


def read(rec):
    lat = rec["latency_s"]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
