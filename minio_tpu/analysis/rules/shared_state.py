"""shared-state: module-global writes in code imported into worker
processes.

The multi-process data plane (parallel/workers.py) imports parts of
this package into SPAWNED worker processes.  A module-level mutable
global written at runtime is per-process state there: the HTTP front's
copy and every worker's copy silently diverge — counters under-count,
caches double-allocate, toggles disagree — and nothing crashes, which
is exactly why it needs a review-time check (ISSUE 8 satellite).

Scope: modules on the worker import surface (the transitive imports of
the worker entry, listed in WORKER_SURFACE — extend it when the worker
grows a new dependency).  Detection:

* the `global NAME` write idiom — the explicit way CPython marks
  function-scope writes to module state;
* function-scope assignment to an attribute of a module-level CLASS or
  an imported MODULE (`SomeClass.cache = ...`, `local_mod.FSYNC = x`,
  `cls.table = ...`) — the same per-process divergence wearing an
  attribute spelling, the ISSUE 10 extension: class attributes are
  module state with extra steps.

In-place mutation of module-level containers (dict/list updates) is
out of scope for now; the repo's convention routes those through the
same `global`-guarded helpers (arena pools, singletons), and flagging
every `.append` would drown the signal.

A flagged site is either a bug (state the front and workers must
agree on) or intentionally process-local (a per-process buffer pool, a
per-process lazy singleton) — the latter carries a reasoned pragma:

    global _pool  # lint: allow(shared-state): per-process staging pool by design — each worker owns its drives' buffers
"""

from __future__ import annotations

import ast

from ..core import Finding, rule

#: modules imported into data-plane worker processes (the worker entry
#: plus its lazy imports: storage, erasure codec/bitrot, host ops).
WORKER_SURFACE = (
    "parallel/workers.py",
    "storage/local.py",
    "storage/errors.py",
    "storage/xlmeta.py",
    "erasure/coding.py",
    "erasure/batcher.py",
    "erasure/bitrot.py",
    "erasure/stagestats.py",
    "ops/host.py",
    "ops/gf256.py",
    "ops/residency.py",
    "utils/deadline.py",
    "utils/tracing.py",
    "utils/hashing.py",
)


def _module_scope_names(tree):
    """(class names, imported-module aliases) defined at module level —
    the receivers whose attribute writes are module state."""
    classes: set[str] = set()
    modules: set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            classes.add(node.name)
    # imports anywhere (the repo lazy-imports heavy deps at function
    # scope): an attribute write through ANY module alias is module
    # state of that module, wherever the alias was bound
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                modules.add(a.asname or a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                name = a.asname or a.name
                # `from x import y as mod`: treat lower_snake aliases
                # that end in _mod (the repo idiom for module imports)
                # plus bare module-looking names conservatively
                if name.endswith("_mod") or name.islower():
                    modules.add(name)
    return classes, modules


def _own_nodes(fn):
    """fn's statements excluding nested def/lambda bodies — each nested
    function is visited as its own fn (no duplicate findings, and the
    `cls` check reads the right signature)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))
        yield node


def _function_attr_writes(tree):
    """Yield (node, receiver, attr, in_classmethod_cls) for attribute
    assignments at function scope."""
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        first_arg = fn.args.args[0].arg if fn.args.args else ""
        for node in _own_nodes(fn):
            if not isinstance(node, (ast.Assign, ast.AugAssign)):
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name):
                    yield (node, t.value.id, t.attr,
                           first_arg == "cls" and t.value.id == "cls")


@rule("shared-state",
      "module-global or class/module-attribute write in a module "
      "imported into worker processes is per-process state (front and "
      "workers silently diverge); pragma it as intentionally "
      "process-local or lift it into explicit cross-process plumbing")
def check(module, project):
    path = module.path.replace("\\", "/")
    if not any(path.endswith(s) for s in WORKER_SURFACE):
        return []
    out = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Global):
            continue
        names = ", ".join(node.names)
        out.append(Finding(
            module.path, node.lineno, node.col_offset, "shared-state",
            f"function writes module global(s) {names} in a module "
            "imported into data-plane worker processes — each process "
            "gets its own copy and they silently diverge; if this "
            "state is intentionally per-process (buffer pool, lazy "
            "singleton), say so with a reasoned pragma"))
    classes, modules = _module_scope_names(module.tree)
    for node, recv, attr, is_cls in _function_attr_writes(module.tree):
        if recv in ("self",):
            continue
        if is_cls or recv in classes:
            what = f"class attribute {recv}.{attr}"
        elif recv in modules:
            what = f"module attribute {recv}.{attr}"
        else:
            continue
        out.append(Finding(
            module.path, node.lineno, node.col_offset, "shared-state",
            f"function writes {what} in a module imported into "
            "data-plane worker processes — class/module attributes are "
            "module state with extra steps: each process mutates its "
            "own copy and they silently diverge; if per-process is the "
            "intent, say so with a reasoned pragma"))
    return out
