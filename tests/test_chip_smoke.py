"""chip_smoke.py and geomx_tpu.runtime stay honest off the chip: no TPU
means a nonzero exit and no result line (never a silent CPU fallback),
and the compile cache is placed from outside or at one fixed path."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from geomx_tpu import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_require_tpu_raises_on_cpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        runtime.require_tpu()
    assert runtime.device_stamp()["platform"] == "cpu"


def test_compile_cache_placed_from_outside_or_fixed(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert runtime.setup_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert runtime.setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_native_library_is_named_by_its_source_hash(tmp_path, monkeypatch):
    """A binary that does not match the .cc in the checkout is never
    loaded: the name carries the source hash, so an edit means a new
    name (file times do not survive a copy of the tree)."""
    from geomx_tpu import native_lib

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    monkeypatch.setattr(native_lib, "NATIVE_DIR", str(tmp_path))
    src = tmp_path / "probe.cc"
    src.write_text('extern "C" int probe() { return 1; }\n')
    first = native_lib.ensure_built("probe", ["-O0"])
    assert os.path.exists(first)
    assert native_lib.ensure_built("probe", ["-O0"]) == first
    src.write_text('extern "C" int probe() { return 2; }\n')
    second = native_lib.ensure_built("probe", ["-O0"])
    assert second != first and os.path.exists(second)
