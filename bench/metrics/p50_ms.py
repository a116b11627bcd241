"""Median over every request due in the window of (answer resolved -
request due), in ms. A request never answered counts with the time it was
waited for."""
import numpy as np


def read(run):
    done = np.where(run.ok, run.done, run.deadline)
    return float(np.percentile((done - run.due) * 1e3, 50))
