"""The layering gate (``tools/layercheck.py``) stays clean and sharp.

CI's lint job runs the same script; having it in tier-1 means a stray
``import asyncio`` (or a transitive hop into JAX) inside the sans-I/O
scheduling core fails the suite everywhere, not just where the lint job
runs.  The unit tests drive the AST walker on synthetic trees so both
directions are covered: it must flag real violations (including
transitive and conditional ones) and must not flag clean layers.
"""

import os
import subprocess
import sys
import textwrap

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _layercheck():
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    try:
        import layercheck
    finally:
        sys.path.pop(0)
    return layercheck


def _write_tree(root, files):
    for rel, body in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            f.write(textwrap.dedent(body))


def test_repo_layering_is_clean():
    res = subprocess.run(
        [sys.executable, os.path.join("tools", "layercheck.py")],
        capture_output=True, text=True, cwd=_ROOT, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "layer check clean" in res.stdout


def test_direct_violation_flagged(tmp_path):
    lc = _layercheck()
    _write_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/core.py": "import asyncio\n",
    })
    v = lc.check_contract("pkg", ("asyncio",), src=str(tmp_path))
    assert len(v) == 1 and "must not reach asyncio" in v[0], v


def test_transitive_violation_flagged(tmp_path):
    # pkg -> helper (outside pkg, same tree) -> socket: the walker must
    # follow the edge out of the root package and still flag it
    lc = _layercheck()
    _write_tree(tmp_path, {
        "pkg/__init__.py": "from helper import thing\n",
        "helper.py": "import socket\n\nthing = 1\n",
    })
    v = lc.check_contract("pkg", ("socket",), src=str(tmp_path))
    assert v and "socket" in v[0], v


def test_conditional_and_from_imports_flagged(tmp_path):
    # an import inside a function body (lazy) and a ``from jax import
    # numpy`` both count — laziness is still coupling
    lc = _layercheck()
    _write_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/lazy.py": "def f():\n    import jax\n    return jax\n",
        "pkg/fromimp.py": "from jax import numpy as jnp\n",
    })
    v = lc.check_contract("pkg", ("jax",), src=str(tmp_path))
    assert len(v) == 2, v


def test_relative_imports_resolve(tmp_path):
    # ``from .sibling import x`` where sibling imports a forbidden
    # module: relative edges must resolve against the package
    lc = _layercheck()
    _write_tree(tmp_path, {
        "pkg/__init__.py": "from .sub import x\n",
        "pkg/sub.py": "from ._impl import x\n",
        "pkg/_impl.py": "import ssl\nx = 1\n",
    })
    v = lc.check_contract("pkg", ("ssl",), src=str(tmp_path))
    assert v and "_impl.py" in v[0], v


def test_clean_layer_passes(tmp_path):
    lc = _layercheck()
    _write_tree(tmp_path, {
        "pkg/__init__.py": "from . import core\n",
        "pkg/core.py": "import math\nimport heapq\n"
                       "from dataclasses import dataclass\n",
    })
    assert lc.check_contract("pkg", ("asyncio", "socket", "jax"),
                             src=str(tmp_path)) == []


def test_missing_package_reported(tmp_path):
    lc = _layercheck()
    v = lc.check_contract("nope", ("asyncio",), src=str(tmp_path))
    assert v and "not found" in v[0]


def test_sched_contract_is_registered():
    # the gate only protects what its CONTRACTS table names — make sure
    # the sched purity promise can't be dropped silently
    lc = _layercheck()
    assert "repro.transfer.sched" in lc.CONTRACTS
    banned = lc.CONTRACTS["repro.transfer.sched"]
    for must in ("asyncio", "socket", "jax"):
        assert must in banned


def test_obs_contract_is_registered():
    # the recorder promises to load no JAX and no I/O stack of its own
    lc = _layercheck()
    assert "repro.obs" in lc.CONTRACTS
    for must in ("asyncio", "socket", "jax"):
        assert must in lc.CONTRACTS["repro.obs"]
