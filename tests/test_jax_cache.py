"""The compile-cache rule (utils/jax_cache.py): where
JAX_COMPILATION_CACHE_DIR is set the program sets no directory; where it
is not, the directory is one fixed path inside the checkout, whatever
the CWD; and in no case does arming the cache start a JAX backend (a
parent that only orchestrates children must stay off the chip).

Each case needs its own environment before `import jax`, so each runs in
a child process.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = """
import json
from tendermint_tpu.utils.jax_cache import CHECKOUT_CACHE_DIR, enable_persistent_cache
returned = enable_persistent_cache()
again = enable_persistent_cache()  # idempotent
import jax
from jax._src import xla_bridge
print(json.dumps({
    "returned": returned,
    "again": again,
    "config_dir": jax.config.jax_compilation_cache_dir,
    "checkout_dir": CHECKOUT_CACHE_DIR,
    "backend_started": xla_bridge.backends_are_initialized(),
}))
"""


def _run(tmp_path, **env_overrides) -> dict:
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    }
    env["PYTHONPATH"] = str(_REPO)
    env.update(env_overrides)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=env,
        cwd=tmp_path,  # not the checkout: the default must not depend on it
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_dir_is_left_alone(tmp_path):
    there = str(tmp_path / "some" / "dir")
    out = _run(tmp_path, JAX_COMPILATION_CACHE_DIR=there)
    assert out["config_dir"] == there  # JAX's own reading of its variable
    assert out["returned"] == out["again"] == there
    assert out["backend_started"] is False


def test_default_is_one_fixed_path_in_the_checkout(tmp_path):
    out = _run(tmp_path)
    want = str(_REPO / ".jax_cache")
    assert out["checkout_dir"] == want
    assert out["config_dir"] == out["returned"] == out["again"] == want
    assert out["backend_started"] is False
    # another process, another CWD, another time: the same directory
    other = tmp_path / "elsewhere"
    other.mkdir()
    assert _run(other)["config_dir"] == want


def test_cpu_process_caches_nothing_and_starts_no_backend(tmp_path):
    out = _run(tmp_path, JAX_PLATFORMS="cpu")
    assert out["returned"] is None and out["config_dir"] is None
    assert out["backend_started"] is False
