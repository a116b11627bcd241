"""The batched XLA LexBFS + PEO test (``jax_fast``'s ``lexbfs_fast`` and
``peo_check`` under ``vmap``) that the ``sharded`` backend jits over a
batch-sharded mesh (``repro.engine.mesh.make_mesh_verdicts``).

It is found in the device trace by its jitted module's name.
"""
#: Device-trace line and name pattern of the kernel's events.
LINE = "XLA Modules"
PATTERN = r"jit_verdicts\b"
#: Unit kinds (the session's ``verdict_kind``) this kernel runs.
KINDS = ("verdict",)


def bytes_moved(n_pad: int, batch: int) -> int:
    """Bytes the unit must move at least: the bool adjacency read once
    (one byte per entry) and the bool verdicts written."""
    return batch * n_pad * n_pad + batch


def vpu_ops(n_pad: int, batch: int) -> int:
    """Element operations of LexBFS and the PEO test: about four passes
    over n_pad lanes per visited vertex, as for the fused kernel."""
    return 4 * batch * n_pad * n_pad
