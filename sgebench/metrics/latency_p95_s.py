"""95th percentile of client latency (``prepare`` start to terminal
status) over every query completed inside the window; the number of
queries it is taken over is printed as ``latency_samples``."""

import numpy as np


def read(run):
    lat = [r.t_end - r.t_start for r in run.completed()]
    return float(np.percentile(lat, 95)) if lat else None
