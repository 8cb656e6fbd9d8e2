"""Layering gate: the sans-I/O scheduling core stays sans-I/O.

``repro.transfer.sched`` exists so the MDTP allocator's decision code
can be driven by the real socket client, the fleet manager, simulators,
and bare unit tests alike.  That only holds while the package (and
everything it imports, transitively, inside ``repro``) touches neither
the event loop, nor sockets, nor JAX — one stray convenience import
silently couples every consumer to the transport/accelerator stack and
breaks import-without-JAX deployments.

This script walks the import graph statically (AST — nothing is
executed, so a violation cannot hide behind an import-time side effect):
starting from every module of each *root* package, it resolves
``import`` / ``from ... import`` statements, follows edges into modules
under ``src/``, and reports any reachable import of a *forbidden*
module.  Conditional imports count — an import inside ``if TYPE_CHECKING:``
or a function body is still a coupling the gate exists to forbid (the
one exception: ``from __future__`` is ignored, and stdlib/third-party
modules other than the forbidden list are allowed — "pure" here means
no I/O/JAX, not no stdlib).

Usage::

    python tools/layercheck.py            # exit 1 on violations

Checked contracts (``CONTRACTS``): each maps a root package to the
module prefixes it must never reach.  Add a row when a new layer makes
a purity promise.
"""

from __future__ import annotations

import ast
import os
import sys

#: root package -> forbidden module prefixes (matched against the full
#: dotted name of every import reachable from the root).
CONTRACTS = {
    "repro.transfer.sched": (
        "asyncio", "socket", "selectors", "ssl",
        "jax", "jaxlib",
        "repro.core.jax_alloc", "repro.core.jax_sim",
        "repro.core.autotune", "repro.core.online",
        "repro.transfer.client", "repro.transfer.server",
        "repro.transfer.manager", "repro.transfer.transport",
    ),
    # the in-program recorder: stdlib only, so the scheduling core or a
    # JAX-less deployment can record without loading the stack
    "repro.obs": (
        "asyncio", "socket", "selectors", "ssl",
        "jax", "jaxlib",
    ),
}

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")


def _module_path(name: str, src: str) -> str | None:
    """Filesystem path of dotted module ``name`` under ``src`` (package
    ``__init__.py`` or plain module), None when it is not ours."""
    parts = name.split(".")
    pkg = os.path.join(src, *parts)
    if os.path.isfile(os.path.join(pkg, "__init__.py")):
        return os.path.join(pkg, "__init__.py")
    mod = pkg + ".py"
    if os.path.isfile(mod):
        return mod
    return None


def _package_modules(root: str, src: str) -> list[str]:
    """Every module of dotted package ``root`` (recursively), by walking
    the tree — the gate must see modules nobody imports yet."""
    path = os.path.join(src, *root.split("."))
    if os.path.isfile(path + ".py"):
        return [root]
    out = []
    for dirpath, dirnames, filenames in os.walk(path):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        rel = os.path.relpath(dirpath, src).replace(os.sep, ".")
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            out.append(rel if name == "__init__.py"
                       else f"{rel}.{name[:-3]}")
    return out


def _imports_of(path: str, module: str) -> list[tuple[str, int]]:
    """``(dotted_name, lineno)`` for every import statement in the file.

    Relative imports resolve against ``module`` (the file's own dotted
    name); ``from pkg import name`` yields both ``pkg`` and
    ``pkg.name`` so a submodule pulled in via ``from`` is followed.
    """
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    pkg_parts = module.split(".")
    if os.path.basename(path) != "__init__.py":
        pkg_parts = pkg_parts[:-1]          # containing package
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                out.append((alias.name, node.lineno))
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            if node.level:
                base = pkg_parts[:len(pkg_parts) - (node.level - 1)]
                stem = ".".join(base + ([node.module] if node.module else []))
            else:
                stem = node.module or ""
            if stem:
                out.append((stem, node.lineno))
            for alias in node.names:
                if alias.name != "*" and stem:
                    out.append((f"{stem}.{alias.name}", node.lineno))
    return out


def _forbidden(name: str, prefixes: tuple[str, ...]) -> bool:
    return any(name == p or name.startswith(p + ".") for p in prefixes)


def check_contract(root: str, prefixes: tuple[str, ...],
                   src: str = _SRC) -> list[str]:
    """Violation strings for one contract (empty = clean)."""
    src = os.path.abspath(src)
    seen: set[str] = set()
    queue = _package_modules(root, src)
    if not queue:
        return [f"{root}: package not found under {src}"]
    violations = []
    while queue:
        mod = queue.pop()
        if mod in seen:
            continue
        seen.add(mod)
        path = _module_path(mod, src)
        if path is None:
            continue                        # stdlib/third-party: not walked
        flagged: set[tuple[str, int]] = set()
        for name, lineno in _imports_of(path, mod):
            if _forbidden(name, prefixes):
                # one finding per import statement: ``from jax import
                # numpy`` yields jax AND jax.numpy — report the first
                if (path, lineno) not in flagged:
                    flagged.add((path, lineno))
                    violations.append(
                        f"{os.path.relpath(path, src)}:{lineno}: {root} "
                        f"must not reach {name}")
                continue
            # follow edges into our own tree (prefix chain: ``import
            # a.b.c`` loads a and a.b too)
            parts = name.split(".")
            for i in range(1, len(parts) + 1):
                prefix = ".".join(parts[:i])
                if prefix not in seen and _module_path(prefix, src):
                    queue.append(prefix)
    return sorted(set(violations))


def main(argv=None) -> int:
    violations = []
    for root, prefixes in CONTRACTS.items():
        violations += check_contract(root, prefixes)
    if violations:
        for v in violations:
            print(v)
        print(f"{len(violations)} layering violation(s)")
        return 1
    print("layer check clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
