"""What the compile-cache key of a verify executable reads.

A Mosaic kernel's serialized body is an operand of its `tpu_custom_call`,
so it is inside the key of every executable that holds a `pallas_call`,
and the body carries the file and line of its ops' Python frames. The
rule (`utils/jax_cache.py`, `ops/ed25519_pallas.py`): after
`enable_persistent_cache()` the body names the kernel's own file and no
caller, so a line added in `services/` or in the host half of
`ops/ed25519_tables.py` re-keys nothing.

Nothing compiles and nothing runs here: each kernel entry is lowered for
the TPU on the CPU (2-8 s each), through a direct call and through two
wrappers on other lines, and the bodies are hashed. The option is read at
a process's first lowering, so each mode is a child process of its own:
this file run as a script.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

_REPO = pathlib.Path(__file__).resolve().parents[1]
_OPS = _REPO / "tendermint_tpu" / "ops"
_BODY = re.compile(r"\\22body\\22: \\22([^\\]+)\\22")

ENTRIES = ("sum_entries", "fused_chain", "ladder", "verify_tables")
# `verify_tables_kernel` is a `jit` of its own, traced once a process whoever
# calls, so one process cannot see its callers move its body; it is here for
# the named scopes on its path.
MOVED_BY_CALLERS = ENTRIES[:3]


# -- the child --------------------------------------------------------------------


def _entry(name: str):
    """(function, argument shapes) of a kernel entry at a launch's shape."""
    import jax
    import jax.numpy as jnp

    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype)

    if name == "sum_entries":
        from tendermint_tpu.ops.ed25519_pallas import _sum_entries_pallas

        return _sum_entries_pallas, [shape((96, 1024, 60))]
    if name == "fused_chain":
        # called directly: `verify_tables_kernel(impl="fused")` interprets
        # off the TPU and has no Mosaic body
        from tendermint_tpu.ops.ed25519_pallas import _fused_chain_pallas

        def fused(sb, tables, digits):
            return _fused_chain_pallas(sb, tables, digits, 128, 8, interpret=False)

        return fused, [
            shape((64, 16, 60)),
            shape((64, 16, 60, 128), jnp.int16),
            shape((1024, 128)),
        ]
    if name == "ladder":
        from tendermint_tpu.ops.ed25519_ladder_pallas import _ladder_pallas

        def ladder(gtab, digits):
            return _ladder_pallas(gtab, digits, 128)

        return ladder, [shape((1, 4, 60, 8, 128)), shape((1, 253, 8, 128))]
    from tendermint_tpu.ops.ed25519_tables import verify_tables_kernel

    def tables(a_tables, s, h, r):
        return verify_tables_kernel(a_tables, s, h, r, impl="pallas")

    lanes = shape((1024, 32), jnp.uint8)
    return tables, [shape((64, 16, 60, 1024), jnp.int16), lanes, lanes, lanes]


def _through_one(fn, *args):
    return fn(*args)


def _through_two(fn, *args):
    # on another line than `_through_one`, and a frame deeper

    return _through_one(fn, *args)


def _bodies(name: str) -> list[str]:
    """A hash of the Mosaic bodies of `name`'s lowering: called directly,
    and through each wrapper."""
    import jax

    fn, shapes = _entry(name)
    callers = (fn, lambda *a: _through_one(fn, *a), lambda *a: _through_two(fn, *a))
    out = []
    for call in callers:
        text = jax.jit(call).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
        found = _BODY.findall(text)
        assert found, f"{name}: no tpu_custom_call body in the lowering"
        out.append(hashlib.sha256("".join(found).encode()).hexdigest())
    return out


def _child(mode: str) -> None:
    if mode == "armed":
        from tendermint_tpu.utils.jax_cache import enable_persistent_cache

        enable_persistent_cache()
    names = ENTRIES if mode == "armed" else MOVED_BY_CALLERS
    print(json.dumps({name: _bodies(name) for name in names}))


# -- the tests --------------------------------------------------------------------


@pytest.fixture(scope="module")
def bodies():
    """{mode: {entry: [body hash of each caller]}}, a child a mode, both at
    once: `armed` has called `enable_persistent_cache()`, `default` has not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(_REPO))
    env.pop("JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS", None)
    children = {
        mode: subprocess.Popen(
            [sys.executable, __file__, mode],
            env=env,
            cwd=_REPO,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for mode in ("armed", "default")
    }
    out = {}
    for mode, child in children.items():
        stdout, stderr = child.communicate(timeout=600)
        assert child.returncode == 0, stderr[-2000:]
        out[mode] = json.loads(stdout.strip().splitlines()[-1])
    return out


@pytest.mark.parametrize("entry", ENTRIES)
def test_a_callers_lines_are_not_in_the_kernels_body(bodies, entry):
    direct, one, two = bodies["armed"][entry]
    assert direct == one == two


@pytest.mark.parametrize("entry", MOVED_BY_CALLERS)
def test_without_the_setting_they_are(bodies, entry):
    """The case the parent failed, and the proof that the hash can see."""
    assert len(set(bodies["default"][entry])) == 3


def _imports(path: pathlib.Path) -> set[str]:
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            found.add(node.module or "")
    return found


def test_the_kernels_file_imports_no_caller():
    ours = {m for m in _imports(_OPS / "ed25519_pallas.py") if m.startswith("tendermint_tpu")}
    assert ours == {"tendermint_tpu.ops.ed25519_kernel"}
    # and the limb module brings in nothing of ours when it is imported
    kernel = ast.parse((_OPS / "ed25519_kernel.py").read_text())
    top = {
        (node.module or "")
        for node in kernel.body
        if isinstance(node, ast.ImportFrom)
    }
    assert not any(m.startswith("tendermint_tpu") for m in top)


def test_every_pallas_call_lives_in_a_kernels_file():
    sites = {
        path.relative_to(_REPO).as_posix()
        for path in (_REPO / "tendermint_tpu").rglob("*.py")
        if "pallas_call(" in path.read_text()
    }
    assert sites == {
        "tendermint_tpu/ops/ed25519_pallas.py",
        "tendermint_tpu/ops/ed25519_ladder_pallas.py",
    }


if __name__ == "__main__":
    _child(sys.argv[1])
