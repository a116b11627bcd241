"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes.

The script itself refuses to run without a TPU; these tests drive its
phase functions directly (Pallas kernels in interpret mode) so a wrong
path, argument or check is caught before a chip run.
"""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""                    # no result line
    assert "no TPU" in captured.err


def test_graphs_pad_to_bucket_and_mix_verdicts(smoke):
    graphs = smoke.make_graphs(64, 5, seed=1)
    assert all(32 < g.n_nodes <= 64 for g in graphs)
    want = smoke.reference_verdicts(graphs)
    assert want.any() and not want.all()


@pytest.mark.parametrize("name", ["jax_fast", "pallas_peo"])
def test_verdict_and_certified_phases(smoke, name):
    verdicts, keys, backend = smoke.run_phase(
        name, ((16, 4), (32, 2)), "cpu")
    assert sorted(verdicts) == [16, 32]
    _, wkeys, _ = smoke.run_phase(
        name, ((32, 2),), "cpu", want_witness=True)
    kinds = {(k[2], k[3]) for k in keys + wkeys}
    assert (backend.verdict_kind(16), 16) in kinds
    assert (backend.witness_kind(32), 32) in kinds


def test_path_check_rejects_interpreted_pallas(smoke):
    from repro.engine.backends import PallasPeoBackend

    with pytest.raises(smoke.SmokeError, match="interpret"):
        smoke.check_pallas_paths([], PallasPeoBackend(interpret=True))


def test_sharded_phase_on_one_device(smoke):
    keys, backend = smoke.sharded_phase("cpu", buckets=((16, 4), (32, 2)))
    assert {(k[2], k[3]) for k in keys} == {("verdict", 16), ("verdict", 32)}
    with pytest.raises(smoke.SmokeError, match="interpret"):
        smoke.check_sharded_paths(keys, backend)


def test_sharded_phase_through_the_fused_program(smoke, monkeypatch):
    """The phase as a TPU runs it, the fused kernel per shard (here
    interpreted, on a one-device mesh), steered onto the fused kind that a
    CPU host would not choose."""
    from repro.engine.backends import ShardedBackend

    monkeypatch.setattr(ShardedBackend, "verdict_kind",
                        lambda self, n_pad: "fused")
    keys, _ = smoke.sharded_phase("cpu", buckets=((16, 4), (32, 2)))
    assert {(k[2], k[3]) for k in keys} == {("fused", 16), ("fused", 32)}
