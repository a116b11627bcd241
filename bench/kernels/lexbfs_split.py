"""The split pipeline above the fused kernel's bucket cap: per graph, the
jitted XLA LexBFS (``repro.core.lexbfs.lexbfs``) and then the two-kernel
Pallas PEO test (``peo_check_pallas``), two dispatches per graph.

It is found in the device trace by its jitted modules' names.
"""
#: Device-trace line and name pattern of the kernel's events.
LINE = "XLA Modules"
PATTERN = r"jit_(lexbfs|peo_check_pallas)\b"
#: Unit kinds (the session's ``verdict_kind``) this kernel runs.
KINDS = ("verdict",)


def bytes_moved(n_pad: int, batch: int) -> int:
    """Bytes the unit must move at least: the bool adjacency read once
    (one byte per entry), the int32 order and the verdict written. The
    same count whatever implements the test."""
    return batch * n_pad * n_pad + batch * n_pad * 4 + batch


def vpu_ops(n_pad: int, batch: int) -> int:
    """Element operations of LexBFS and the PEO test: about four passes
    over n_pad lanes per visited vertex, as for the fused kernel."""
    return 4 * batch * n_pad * n_pad
