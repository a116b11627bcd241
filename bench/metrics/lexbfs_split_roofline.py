"""Share of the HBM roofline the split pipeline (XLA LexBFS, then the
Pallas PEO test) reaches, in %: the least time the bytes it must move
take at the chip's published HBM bandwidth, over its device time."""
from bench.trace import roofline_pct


def read(run):
    return roofline_pct(run.dev, run.peaks, "lexbfs_split")
