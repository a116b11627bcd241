"""Median of the ``queue`` span (submit to unit start) over the requests
answered in the window, in ms: the service's admission wait."""
import numpy as np


def read(run):
    ms = [r.find("queue").duration_ms for r in run.spans
          if r.find("queue") is not None and run.t0 <= r.t_end <= run.t_end]
    return float(np.percentile(ms, 50)) if ms else None
