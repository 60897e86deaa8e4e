"""``chip_smoke.py`` off the chip, and where the compile cache goes.

The smoke must fail loudly — non-zero exit, no result line — wherever
it cannot reach a TPU or the repository beside it; the chip run itself
is not reproducible here.  Each case runs in a child process pinned to
the CPU backend.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(args, cwd, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def _result_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_without_tpu_or_repo(tmp_path, where):
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    r = _run([str(script)], cwd=script.parent)
    assert r.returncode != 0
    assert _result_lines(r.stdout) == []
    want = "no TPU" if where == "repo" else "src/repro is not beside"
    assert want in r.stderr


_PROBE = ("import sys; sys.path.insert(0, 'src'); import jax; "
          "from repro.utils.compile_cache import use_compile_cache; "
          "print(use_compile_cache()); "
          "print(jax.config.jax_compilation_cache_dir)")
# one compile, so the cache has an entry to write
_COMPILE = "; jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(3))"


def test_compile_cache_defaults_to_repo_dir():
    r = _run(["-c", _PROBE], cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(REPO / ".jax_cache")] * 2


def test_compile_cache_env_dir_wins(tmp_path):
    cache = tmp_path / "cache"
    r = _run(["-c", _PROBE + _COMPILE], cwd=REPO,
             JAX_COMPILATION_CACHE_DIR=str(cache),
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(cache)] * 2
    assert any(cache.iterdir())
