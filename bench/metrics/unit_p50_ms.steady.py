"""Median of the ``unit`` span (realize, dispatch and the executable call
of the unit that answered the request) over the requests answered in the
window, in ms: the session's share of the latency."""
import numpy as np


def read(run):
    ms = [r.find("unit").duration_ms for r in run.spans
          if r.find("unit") is not None and run.t0 <= r.t_end <= run.t_end]
    return float(np.percentile(ms, 50)) if ms else None
