"""Device dispatch + Array coherence protocol tests."""

import logging
import os
import pickle
import subprocess
import sys

import numpy
import pytest

from veles_tpu.backends import (BackendRegistry, CPUDevice, Device,
                                NumpyDevice, resolve_backend)
from veles_tpu.memory import Array, roundup, watcher


def test_registry_contents():
    assert set(BackendRegistry.backends) >= {"tpu", "cpu", "numpy"}


def test_dispatch_by_name():
    assert isinstance(Device(backend="numpy"), NumpyDevice)
    assert isinstance(Device(backend="cpu"), CPUDevice)


def test_auto_resolution_prefers_available():
    # under tests JAX is CPU-only, so auto → cpu
    assert resolve_backend("auto") in ("cpu", "tpu")


def test_tpu_by_name_raises_without_a_chip():
    """A chip entry point asks for "tpu" by name: on a host whose JAX
    shows only CPU devices that is an error, never a CPU run."""
    with pytest.raises(RuntimeError, match="no tpu devices"):
        Device(backend="tpu")


def test_auto_says_why_it_left_the_tpu(caplog):
    with caplog.at_level(logging.WARNING):
        assert resolve_backend("auto") == "cpu"
    text = caplog.text
    assert "tpu backend unavailable" in text and "jax_platforms=cpu" in text
    assert "found no TPU and runs on 'cpu'" in text


def test_device_index_past_the_end_raises():
    assert Device(backend="cpu", device_index=7).jax_device.id == 7
    with pytest.raises(ValueError, match="device index 8 out of range"):
        Device(backend="cpu", device_index=8)


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_PROBE = """
import jax
from veles_tpu.backends import Device
Device(backend="cpu")
print(jax.config.jax_compilation_cache_dir)
"""


def _probe_cache_dir(tmp_path, **env):
    """jax_compilation_cache_dir as a FRESH interpreter resolves it,
    started from another directory and with another $HOME."""
    tmp_path.mkdir()
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(PYTHONPATH=_REPO, HOME=str(tmp_path), **env)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=base, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_is_a_fixed_dir_in_the_checkout(tmp_path):
    first = _probe_cache_dir(tmp_path / "a")
    second = _probe_cache_dir(tmp_path / "b")
    assert first == second
    assert first.startswith(os.path.join(_REPO, ".veles_cache", "xla"))
    # nothing was written under either $HOME
    assert not list((tmp_path / "a").iterdir())
    assert not list((tmp_path / "b").iterdir())


def test_compile_cache_dir_from_the_environment_is_left_alone(tmp_path):
    chosen = str(tmp_path / "outside")
    assert _probe_cache_dir(tmp_path / "home",
                            JAX_COMPILATION_CACHE_DIR=chosen) == chosen


def test_chip_smoke_refuses_a_cpu_before_building_a_model(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], env=env,
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "backend is 'cpu', not 'tpu'" in out.stderr
    assert out.stdout == ""  # no device line, no model, no result


def test_chip_smoke_last_line_is_ok_and_device_only(monkeypatch, capsys):
    """The driver reads the last stdout line as a JSON object with
    exactly ``ok`` and ``device`` {platform, kind, count}; the named
    checks go on their own line before it."""
    import json
    monkeypatch.syspath_prepend(_REPO)
    import chip_smoke
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: device)
    monkeypatch.setattr(chip_smoke, "train",
                        lambda **kw: ({"trained": True}, [6.9], None))
    monkeypatch.setattr(chip_smoke, "kernels", lambda: {"gemm": False})
    monkeypatch.setattr(chip_smoke, "cache_entries", lambda: ("dir", 0))
    assert chip_smoke.main([]) == 1
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": False, "device": device}
    assert "FAILED check: kernel:gemm" in lines
    monkeypatch.setattr(chip_smoke, "kernels", lambda: {"gemm": True})
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert json.loads(lines[-2].split("checks: ", 1)[1]) == {
        "trained": True, "kernel:gemm": True}


def test_unknown_backend_raises():
    with pytest.raises((ValueError, RuntimeError, KeyError)):
        Device(backend="nonexistent")


def test_numpy_device_does_not_exist():
    assert not NumpyDevice().exists


def test_device_pickle_identity():
    dev = Device(backend="cpu")
    dev2 = pickle.loads(pickle.dumps(dev))
    assert dev2.BACKEND == "cpu"


class TestArray(object):
    def test_host_only(self):
        a = Array(numpy.arange(6, dtype=numpy.float32).reshape(2, 3))
        assert a.shape == (2, 3)
        assert a.devmem is a.mem  # no device attached

    def test_upload_download_roundtrip(self):
        dev = Device(backend="cpu")
        a = Array(numpy.arange(6, dtype=numpy.float32).reshape(2, 3))
        a.initialize(dev)
        dm = a.devmem
        assert dm.shape == (2, 3)
        # simulate a device-side update (a jitted step output)
        a.assign_devmem(dm * 2)
        host = a.map_read()
        numpy.testing.assert_allclose(host, numpy.arange(6).reshape(2, 3) * 2)

    def test_map_write_marks_dirty(self):
        dev = Device(backend="cpu")
        a = Array(numpy.zeros((2, 2), numpy.float32))
        a.initialize(dev)
        _ = a.devmem
        a.map_write()[0, 0] = 5.0
        a.unmap()
        assert float(numpy.asarray(a.devmem)[0, 0]) == 5.0

    def test_map_invalidate_skips_download(self):
        dev = Device(backend="cpu")
        a = Array(numpy.zeros((2, 2), numpy.float32))
        a.initialize(dev)
        a.assign_devmem(a.devmem + 7)  # device dirty
        buf = a.map_invalidate()       # host will overwrite: no download
        buf[...] = 1.0
        numpy.testing.assert_allclose(a.map_read(), numpy.ones((2, 2)))

    def test_numpy_device_stays_host(self):
        a = Array(numpy.ones(3))
        a.initialize(NumpyDevice())
        assert a.device is None
        assert a.devmem is a.mem

    def test_pickle_syncs_device_state(self):
        dev = Device(backend="cpu")
        a = Array(numpy.zeros(4, numpy.float32))
        a.initialize(dev)
        a.assign_devmem(a.devmem + 3)
        a2 = pickle.loads(pickle.dumps(a))
        numpy.testing.assert_allclose(a2.mem, 3 * numpy.ones(4))
        assert a2.device is None

    def test_getitem_setitem(self):
        a = Array(numpy.zeros((2, 2)))
        a[0, 1] = 9
        assert a[0, 1] == 9

    def test_watcher_accounting(self):
        dev = Device(backend="cpu")
        before = watcher.total
        a = Array(numpy.zeros((100, 100), numpy.float32))
        a.initialize(dev)
        _ = a.devmem
        assert watcher.total == before + 40000
        a.reset()
        assert watcher.total == before


def test_roundup():
    assert roundup(5, 8) == 8
    assert roundup(16, 8) == 16
