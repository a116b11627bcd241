"""Device part of the backend's executable call, from the launch until
its outputs are ready (span ``device`` under ``dispatch``) per graph
answered, in us; 0 where the program records no such span."""
from bench.stages import stage_seconds


def read(run):
    got = stage_seconds(run.spans, "device")
    return None if got is None else got[0] * 1e6 / got[1]
