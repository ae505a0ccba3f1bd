"""``chip_smoke.py`` on the CPU and the compile-cache rule it follows.

The smoke's phases run end to end at a tiny size (Pallas in interpret
mode, XLA on the CPU) so that a change which breaks its control flow or
its oracle comparison fails here, before any chip run.  ``main`` itself
must refuse to run without a TPU and print no result line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.compile_cache import REPO_CACHE_DIR, use_compile_cache  # noqa: E402

TINY = chip_smoke.Sizes(n_users=160, n_wifi=8000, n_occ=4000)


@pytest.fixture
def cache_dir(tmp_path):
    """Stand in for a set ``JAX_COMPILATION_CACHE_DIR`` so that nothing
    this process compiles lands in the checkout."""
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    yield str(tmp_path)
    jax.config.update("jax_compilation_cache_dir", was)


def test_smoke_phases_match_the_oracle_on_cpu(cache_dir):
    lines = []
    device = chip_smoke.run_smoke(seed=3, sizes=TINY, log=lines.append)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert f"compile cache: {cache_dir}" in lines[0]
    phases = {ln.split(":", 1)[0]: json.loads(ln.split(":", 1)[1])
              for ln in lines if ln.startswith("phase ")}
    assert list(phases) == ["phase K", "phase A", "phase B", "phase O"]
    assert phases["phase B"]["compiled_hits"] > 0
    assert phases["phase O"]["mismatches"] == {
        "exact": 0, "float": 0, "imputations": 0}
    # on the CPU every requested kernel ran in interpret mode
    assert phases["phase B"]["kernel_calls"]["segment_reduce/pallas-interpret"] > 0
    assert phases["phase A"]["kernel_calls"]["bloom_probe/ref"] > 0


def test_smoke_rejects_a_stream_without_group_by(cache_dir, monkeypatch):
    monkeypatch.setattr(chip_smoke, "WORKLOAD_KIND", "high")
    sizes = chip_smoke.Sizes(n_users=40, n_wifi=2000, n_occ=1000,
                             n_queries=1, n_templates=1)
    with pytest.raises(AssertionError, match="no GROUP BY"):
        chip_smoke.run_smoke(seed=1, sizes=sizes, log=lambda _line: None)


def test_smoke_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_smoke_alone_exits_nonzero(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    script = tmp_path / "chip_smoke.py"
    with open(os.path.join(ROOT, "chip_smoke.py")) as fh:
        script.write_text(fh.read())
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_compile_cache_defaults_to_the_checkout():
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert use_compile_cache() == REPO_CACHE_DIR
        assert REPO_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
    checkout's default directory is never named."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(4)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert any(name.endswith("-cache") for name in os.listdir(tmp_path))
