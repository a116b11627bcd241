"""Graphs answered in the measured window, per second of the window."""
import numpy as np


def read(run):
    inside = run.ok & (run.done >= run.t0) & (run.done <= run.t_end)
    return float(np.count_nonzero(inside)) / run.seconds
