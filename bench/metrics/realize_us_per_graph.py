"""Host time of the planner's dense pad (span ``realize``) per graph
answered, in us."""
from bench.spans import units


def read(run):
    us = units(run.spans)
    graphs = sum(u[3] for u in us)
    return sum(u[4] for u in us) * 1e6 / graphs if graphs else None
