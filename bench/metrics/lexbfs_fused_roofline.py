"""Share of the HBM roofline the fused LexBFS+PEO kernel reaches, in %:
the least time the bytes it must move take at the chip's published HBM
bandwidth, over its device time. Bytes bound it: the kernel does no
matrix multiplication, and the published peaks give no VPU rate."""
from bench.trace import roofline_pct


def read(run):
    return roofline_pct(run.dev, run.peaks, "lexbfs_fused")
