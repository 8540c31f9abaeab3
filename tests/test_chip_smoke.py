"""chip_smoke.py refuses to run without a TPU or outside the repository,
and the compile-cache helper it calls places the cache from outside."""
import shutil
import subprocess
import sys
from pathlib import Path

import jax

import repro.utils
from repro.utils import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


def test_chip_smoke_fails_without_tpu(capsys):
    """On the CPU the script exits non-zero before any phase, says why,
    and prints no result line."""
    rc = _chip_smoke().main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    assert '"ok"' not in out


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    """Alone in a directory, the script exits non-zero with a message
    and no result line."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "src/repro is not beside" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper leaves the choice to
    JAX and sets no directory itself."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch):
    """Unset, the cache goes to <checkout>/.jax_cache: the same path on
    every call and in every run."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert Path(repro.utils.__file__).resolve().parents[2] == ROOT
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
