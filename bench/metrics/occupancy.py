"""Mean live requests per executed unit, over the units whose requests
were answered in the run (from the ``exec`` span each unit's requests
share)."""
from bench.spans import units


def read(run):
    us = units(run.spans)
    return sum(u[3] for u in us) / len(us) if us else None
