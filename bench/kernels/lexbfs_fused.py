"""The fused LexBFS+PEO Pallas kernel (``repro.kernels.lexbfs_fused``):
one ``pallas_call`` per unit, the batch as its grid, packed G graphs per
program at n_pad <= 64.

It is found in the device trace by its jitted module's name, since the
``pallas_call`` carries no name of its own.
"""
#: Device-trace line and name pattern of the kernel's events.
LINE = "XLA Modules"
PATTERN = r"jit__fused(_packed)?\b"
#: Unit kinds (the session's ``verdict_kind``) this kernel runs.
KINDS = ("fused", "fused_packed")


def bytes_moved(n_pad: int, batch: int) -> int:
    """Bytes the unit must move at least: the int8 adjacency read once
    (one byte per entry), the int32 orders and the violation counts
    written. The same count whatever implements the kernel."""
    return batch * n_pad * n_pad + batch * n_pad * 4 + batch * 4


def vpu_ops(n_pad: int, batch: int) -> int:
    """Element operations of the visit loop: each of the n_pad steps of
    each graph selects the next vertex (argmax over n_pad ranks), refines
    the n_pad ranks by the visited row, and checks the row against the
    parent's for the PEO test: about four passes over n_pad lanes."""
    return 4 * batch * n_pad * n_pad
