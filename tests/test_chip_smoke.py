"""Rehearse ``chip_smoke.py`` on the CPU at tiny sizes.

The script's phases take their sizes as arguments; these tests pass
small ones (the script itself has no size option). ``main()`` must
refuse a backend that is not a TPU, and so must the script run alone.
"""

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from tensorframes_tpu import memory
from tensorframes_tpu.observability import device
from tensorframes_tpu.utils.tracing import counters


@pytest.fixture
def clock():
    counters.reset()
    return chip_smoke.CompileClock()


def test_phase_frame(clock):
    chip_smoke.phase_frame(clock, n_rows=4096, partitions=4,
                           groups=(10, 300))


def test_phase_serve_reads_allocator_limit(clock, monkeypatch):
    # the CPU reports no allocator limit; stand in for the chip's
    monkeypatch.setattr(device, "watermark", lambda: {
        "live_bytes": 0, "peak_bytes": 0, "limit_bytes": 1 << 34,
        "devices": 1})
    memory._reset()
    try:
        chip_smoke.phase_serve(clock, n_rows=2048, partitions=4)
    finally:
        memory._reset()


def test_phase_serve_refuses_unknown_limit(clock, monkeypatch):
    monkeypatch.setattr(device, "watermark", lambda: None)
    with pytest.raises(AssertionError, match="allocator limit"):
        chip_smoke.phase_serve(clock, n_rows=256, partitions=2)


def test_phase_resnet(clock):
    diff = chip_smoke.phase_resnet(clock, images=4, blocks=2, size=32,
                                   num_classes=10)
    assert diff >= 0.0


def test_phase_mesh_four_devices(clock, monkeypatch):
    # a low threshold sends the 600-key aggregate down the shuffle route
    monkeypatch.setenv("TFT_SHUFFLE_AGG_GROUPS", "500")
    chip_smoke.phase_mesh(clock, chips=4, n_rows=4096, partitions=4,
                          groups=(10, 600), join_rows=512)
    snap = chip_smoke.phase_audit(clock)
    assert snap.get("mesh.shuffle_agg_routes") == 1
    assert snap.get("relational.partitioned_joins") == 1


def test_audit_fails_on_fallback_counter(clock):
    chip_smoke.phase_audit(clock)
    counters.inc("pipeline.sync_fallbacks")
    with pytest.raises(AssertionError, match="pipeline.sync_fallbacks"):
        chip_smoke.phase_audit(clock)


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_script_alone_refuses(tmp_path):
    shutil.copy(chip_smoke.__file__, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode != 0
    assert proc.stdout == ""
