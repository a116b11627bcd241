"""Host int8 conversion of a unit's adjacency before its copy (span
``pack`` under ``dispatch``) per graph answered, in us; 0 where the
program records no such span."""
from bench.stages import stage_seconds


def read(run):
    got = stage_seconds(run.spans, "pack")
    return None if got is None else got[0] * 1e6 / got[1]
