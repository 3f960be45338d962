"""The chip's entry points, checked off the chip.

* ``chip_smoke.py`` refuses to run anywhere but on a TPU, and prints no
  result when it refuses.
* The persistent compilation cache is placed from outside: by
  ``JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise at the fixed
  ``<checkout>/.jax_cache``.

Each check runs in a subprocess, so no JAX configuration leaks into the
test process.
"""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.path.join(REPO, "src"))
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, env=full, cwd=cwd)


_PROBE = ("import jax; from repro.launch.compile_cache import "
          "enable_compile_cache; print(enable_compile_cache()); "
          "print(jax.config.jax_compilation_cache_dir)")


def test_chip_smoke_refuses_the_cpu_and_names_it():
    p = _run([os.path.join(REPO, "chip_smoke.py")])
    assert p.returncode != 0
    assert "'cpu'" in p.stdout and "not a TPU" in p.stdout
    assert '"ok"' not in p.stdout


def test_chip_smoke_alone_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path),
             PYTHONPATH="")
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_compile_cache_honours_the_environment(tmp_path):
    placed = str(tmp_path / "cache")
    p = _run(["-c", _PROBE], JAX_COMPILATION_CACHE_DIR=placed)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [placed, placed]


def test_compile_cache_defaults_to_the_checkout():
    p = _run(["-c", _PROBE])
    assert p.returncode == 0, p.stderr
    fixed = os.path.join(REPO, ".jax_cache")
    assert p.stdout.split() == [fixed, fixed]
