"""chip_smoke.py's phases, run here on the CPU at a few hundred keys.

The script itself refuses any platform but the TPU; the platform it
requires is steered in the test (not through an option of the script),
in this process — no JAX child. What this proves is the script's control
flow and its comparisons; it is not a chip run.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SIZES = {"counter": 160, "gauge": 80, "timer": 120, "set": 36, "llhist": 8}


@pytest.fixture
def smoke(monkeypatch, tmp_path, jax_cache_config):
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "INTERVAL_S", 1.0)
    # CPU servers keep the compile cache off unless a directory is
    # named; the script expects one, as on the chip. JAX read the
    # variable when it was imported, so its config is set here as well
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jit"))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jit"))
    return chip_smoke


def test_refuses_another_platform():
    with pytest.raises(SystemExit, match="JAX found platform 'cpu'"):
        chip_smoke.require_devices(1)


@pytest.mark.parametrize("phase", ["one_chip", "four_chips"])
def test_phase_passes_at_small_size(smoke, phase):
    if phase == "one_chip":
        stamp = smoke.run_one_chip(seed=0, sizes=SIZES, interval_s=1.0)
    else:
        stamp = smoke.run_four_chips(seed=0, sizes=SIZES)
    assert stamp["platform"] == "cpu" and stamp["count"] >= 4


def test_more_shards_than_devices_is_an_error():
    """No virtual-device substitution, no clamping: both numbers named."""
    import jax

    from veneur_tpu.parallel.sharded_server import (build_plane,
                                                    local_shard_devices)

    have = len(jax.local_devices())
    with pytest.raises(ValueError,
                       match=f"{have + 1} shards requested but only {have}"):
        local_shard_devices(have + 1)
    with pytest.raises(ValueError):
        build_plane(have + 1)
    assert len(local_shard_devices(have)) == have
