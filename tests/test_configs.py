"""repro.configs: the service's presets, the bucket grid and the VMEM caps.

``repro.configs`` holds only what the chordality service reads: the
``ServiceConfig`` presets (``configs/service.py``) and the shape plan
(``configs/shapes.py``) — the n_pad and batch buckets every work unit is
compiled against and the VMEM models that cap which kernel serves a
bucket. The last test guards the package boundary: starting the engine
loads no module outside the service's own packages.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import (
    SERVICE_CONFIGS,
    AutotuneConfig,
    ServiceConfig,
    service_config,
)
from repro.configs.shapes import (
    ENGINE_BATCH_BUCKETS,
    ENGINE_NPAD_BUCKETS,
    FUSED_MAX_NPAD,
    FUSED_PACK_MAX_NPAD,
    FUSED_WITNESS_MAX_NPAD,
    TPU_VMEM_BYTES,
    engine_batch_bucket,
    engine_npad_bucket,
    fused_packed_vmem_bytes,
    fused_vmem_bytes,
    fused_witness_vmem_bytes,
)

# ---------------------------------------------------------------------------
# Service presets.
# ---------------------------------------------------------------------------
#: name -> (max_queue, max_batch, max_wait_ms, autotuned)
_PRESETS = {
    "default": (1024, 32, 2.0, False),
    "throughput": (1024, 64, 8.0, False),
    "latency": (1024, 8, 0.5, False),
    "smoke": (64, 8, 1.0, False),
    "autotuned": (1024, 32, 2.0, True),
}


@pytest.mark.parametrize("name", sorted(_PRESETS))
def test_service_config_preset(name):
    cfg = service_config(name)
    assert cfg is SERVICE_CONFIGS[name]
    assert isinstance(cfg, ServiceConfig)
    max_queue, max_batch, max_wait_ms, autotuned = _PRESETS[name]
    assert (cfg.max_queue, cfg.max_batch, cfg.max_wait_ms) == \
        (max_queue, max_batch, max_wait_ms)
    assert isinstance(cfg.autotune, AutotuneConfig) is autotuned
    # a full unit of the preset fills its batch bucket exactly
    assert cfg.max_batch in ENGINE_BATCH_BUCKETS


def test_service_config_presets_are_exactly_these():
    assert sorted(SERVICE_CONFIGS) == sorted(_PRESETS)


def test_service_config_unknown_name_lists_the_presets():
    with pytest.raises(KeyError, match="available:.*autotuned"):
        service_config("Default")


# ---------------------------------------------------------------------------
# n_pad buckets: the smallest grid power of two >= n.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,want", [
    (2, 16), (15, 16), (32, 32), (33, 64), (64, 64), (65, 128),
    (1024, 1024), (1025, 2048), (2048, 2048), (4097, 8192),
])
def test_npad_bucket_is_smallest_grid_power_of_two(n, want):
    assert engine_npad_bucket(n) == want
    assert want == min(b for b in ENGINE_NPAD_BUCKETS if b >= n)


@pytest.mark.parametrize("n,want", [(8193, 16384), (16385, 32768)])
def test_npad_bucket_above_the_grid(n, want):
    assert n > ENGINE_NPAD_BUCKETS[-1]
    assert engine_npad_bucket(n) == want


@pytest.mark.parametrize("n", [0, -1])
def test_npad_bucket_rejects_non_positive_sizes(n):
    with pytest.raises(ValueError, match="graph size must be positive"):
        engine_npad_bucket(n)


# ---------------------------------------------------------------------------
# Batch buckets: round up to the grid, capped at max_batch.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,max_batch,want", [
    (1, 64, 1), (2, 64, 2), (33, 64, 64), (7, 7, 7),
    (65, 128, 128), (1024, 1024, 1024), (1025, 2048, 2048),
])
def test_batch_bucket(b, max_batch, want):
    assert engine_batch_bucket(b, max_batch) == want


def test_batch_bucket_rejects_empty_units():
    with pytest.raises(ValueError, match="batch must be positive"):
        engine_batch_bucket(0, 32)


# ---------------------------------------------------------------------------
# VMEM caps: each kernel's largest bucket is what its VMEM model allows.
# ---------------------------------------------------------------------------
def _largest_fitting(model, budget):
    return max(b for b in ENGINE_NPAD_BUCKETS if model(b) <= budget)


def test_fused_max_npad_is_largest_bucket_under_vmem():
    assert FUSED_MAX_NPAD == _largest_fitting(
        fused_vmem_bytes, TPU_VMEM_BYTES) == 2048


def test_fused_witness_max_npad_is_largest_bucket_under_vmem():
    assert FUSED_WITNESS_MAX_NPAD == _largest_fitting(
        fused_witness_vmem_bytes, TPU_VMEM_BYTES) == 1024
    assert fused_witness_vmem_bytes(2 * FUSED_WITNESS_MAX_NPAD) \
        > TPU_VMEM_BYTES


def test_fused_pack_max_npad_stays_a_small_share_of_vmem():
    # Packing is capped by a share of VMEM, not by all of it: the largest
    # bucket whose packed program stays under 1/64 of the budget.
    assert FUSED_PACK_MAX_NPAD in ENGINE_NPAD_BUCKETS
    assert FUSED_PACK_MAX_NPAD <= FUSED_MAX_NPAD
    assert FUSED_PACK_MAX_NPAD == _largest_fitting(
        fused_packed_vmem_bytes, TPU_VMEM_BYTES // 64) == 64


# ---------------------------------------------------------------------------
# Package boundary.
# ---------------------------------------------------------------------------
_KEPT_PACKAGES = {
    "configs", "core", "engine", "graphs", "kernels", "obs",
    "recognition", "sparse", "witness",
}

_PROBE = r"""
import json, sys
import repro.engine
import repro.configs
print(json.dumps({
    "modules": sorted(m for m in sys.modules if m.startswith("repro.")),
    "configs_public": sorted(
        n for n in vars(repro.configs) if not n.startswith("_")),
}))
"""


def test_engine_import_loads_only_the_service_packages():
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(src)}
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], capture_output=True, text=True,
        check=True, timeout=120, env=env)
    probe = json.loads(out.stdout.strip().splitlines()[-1])
    stray = [m for m in probe["modules"]
             if m.split(".")[1] not in _KEPT_PACKAGES]
    assert stray == []
    # the service's settings and their two modules, and no registry
    assert probe["configs_public"] == sorted([
        "SERVICE_CONFIGS", "AutotuneConfig", "ServiceConfig",
        "service_config", "service", "shapes"])
