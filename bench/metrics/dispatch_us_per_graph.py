"""Time of the backend's executable call (span ``dispatch``: host to
device copy, device, device to host copy) per graph answered, in us."""
from bench.spans import units


def read(run):
    us = units(run.spans)
    graphs = sum(u[3] for u in us)
    return sum(u[5] for u in us) * 1e6 / graphs if graphs else None
