"""Whole-package static call graph: the interprocedural backbone of the
lock/loop discipline rules (ISSUE 19 tentpole).

The server is one aiohttp event loop fronting executor threads, and the
most repeated review-bug class across PRs 7-18 is work that blocks the
loop or wedges the lock graph *two or more calls away* from where the
rule could see it: `rules/locks.py` followed calls one level deep, so a
one-liner helper hid every real instance (the PR 15 under-lock ring
scans, the PR 11 mesh-launch deadlock).  This module builds one parsed
call graph per lint run and answers the three questions those rules
ask:

* **resolution** — who does this call site reach?  Module functions,
  `self.`/`cls.` methods through package-local inheritance (a bounded
  MRO walk), `Class.m()`/`Class()` constructors, module-alias calls
  (`mod.f()`), and attribute receivers whose type is pinned by a
  `self.x = ClassName(...)` constructor assignment.  Dynamic dispatch,
  `__getattr__` delegation (gateway/cache.py) and string-built names
  are documented blind spots: an unresolved call simply has no edge —
  the blocking-terminal TABLES below still classify it by name, so a
  storage op stays a finding even on an untyped receiver.

* **async/sync coloring + executor hops** — every `async def` body is
  loop-colored; following non-hop call edges propagates the color into
  sync callees.  A callable handed to `run_in_executor`, `ctx_submit`,
  `pool.submit`, `service_thread`, `Thread(target=)`,
  `Process(target=)` or `to_thread` runs on another thread: the edge is
  kept (the graph stays complete for lock-order) but marked `hop`, and
  loop-reachability traversal stops there.

* **lock identity** — `with <lockish>:` regions resolve their lock to a
  stable key: ``C:<module>.<Class>.<attr>`` for instance locks (per
  class — two classes' `_mu` are different locks), ``M:<module>.<name>``
  for module-level locks, and a function-scoped fallback for
  parameters/locals that cannot alias across functions.  Per-function
  *acquired-lock summaries* (direct + transitive through non-hop edges)
  feed the lock-order cycle check.

Everything here works on the already-parsed `core.Module` ASTs — the
linter must not import aiohttp/jax — and the graph is built once per
`core.Project` and shared by every rule (`project.callgraph()`).
"""

from __future__ import annotations

import ast

from .core import call_name, expr_source, terminal_name

#: call names whose callable ARGUMENTS run on another thread/process —
#: the executor hops that sever loop-reachability (and lock extent).
HOP_CALLS = {
    "run_in_executor", "ctx_submit", "io_submit", "submit",
    "service_thread", "to_thread", "apply_async", "Thread", "Process",
}

# ---------------------------------------------------------------------------
# blocking terminals (shared with rules/locks.py — one table, two rules)
# ---------------------------------------------------------------------------
#: StorageAPI ops (instrumented.TIMED_OPS): each is a disk touch.
STORAGE_OPS = {
    "make_volume", "list_volumes", "stat_volume", "delete_volume",
    "read_all", "write_all", "rename_file", "create_file",
    "open_file_writer", "append_file", "read_file_stream", "read_file",
    "read_version", "read_xl", "write_metadata", "update_metadata",
    "delete_version", "delete_versions", "free_version_data",
    "rename_data", "list_dir", "walk_dir", "verify_file", "check_parts",
    "disk_info", "read_at", "read_blocks",
}

#: unconditional blockers by terminal callee name.
BLOCKING_CALLS = {
    "sleep": "time.sleep blocks",
    "result": "Future.result() can wait a full RPC/disk timeout",
    "urlopen": "network I/O",
    "getaddrinfo": "DNS resolution",
    "fsync": "os.fsync rides the device queue",
    "fdatasync": "os.fdatasync rides the device queue",
}

#: RPC entry points (distributed/rpc.py RpcClient and peers).
RPC_CALLS = {"call", "call_stream", "broadcast", "invoke"}

#: subprocess spawns/waits — a fork+exec (and its wait) off the loop.
SUBPROCESS_CALLS = {"check_output", "check_call", "communicate",
                    "Popen", "run"}

#: blocking socket ops, gated on a socket-ish receiver name.
SOCKET_CALLS = {"recv", "recv_into", "sendall", "connect", "accept"}

LOCKISH = ("mu", "mtx", "mutex", "lock", "lk", "cv", "cond", "condition")
_QUEUEISH = ("queue", "inbox", "jobs")
_THREADISH = ("thread", "worker", "probe", "proc")
_SOCKISH = ("sock", "socket", "conn")


def is_lockish(name: str) -> bool:
    low = name.lower().lstrip("_")
    return any(low == t or low.endswith("_" + t) or low.startswith(t + "_")
               or (t in ("mutex", "lock") and t in low)
               for t in LOCKISH)


def is_condish(name: str) -> bool:
    low = name.lower().lstrip("_")
    return any(t in low for t in ("cv", "cond"))


def _queueish(name: str) -> bool:
    low = name.lower()
    return (any(t in low for t in _QUEUEISH)
            or low in ("q", "_q") or low.endswith("_q"))


def _threadish(name: str) -> bool:
    low = name.lower().lstrip("_")
    return low in ("t", "th") or any(t in low for t in _THREADISH)


def _sockish(name: str) -> bool:
    low = name.lower().lstrip("_")
    return any(t in low for t in _SOCKISH)


def classify_blocking(node: ast.Call, *, lock_src: str = "",
                      is_cond: bool = False) -> str | None:
    """The shared blocking-terminal table: the reason `node` blocks the
    calling thread, or None.  `lock_src`/`is_cond` enable the one
    sanctioned exemption — `cv.wait()` on the HELD condition releases
    it, so under `with cv:` it is not a blocker."""
    name = call_name(node)
    last = name.rsplit(".", 1)[-1]
    recv = node.func.value if isinstance(node.func, ast.Attribute) else None
    recv_name = terminal_name(recv) if recv is not None else ""
    if last in BLOCKING_CALLS:
        if last == "sleep" and recv_name == "asyncio":
            return None  # asyncio.sleep parks the task, not the thread
        return BLOCKING_CALLS[last]
    if last in ("wait", "wait_for"):
        if recv_name == "asyncio":
            return None  # asyncio.wait/wait_for are awaitables
        if recv is not None and is_cond \
                and expr_source(recv) == lock_src:
            return None  # cond.wait() on the held condition releases it
        return f"`{name}` parks the thread until signaled"
    if last == "acquire" and recv is not None and is_lockish(recv_name):
        # an explicit blocking acquire can park arbitrarily long; the
        # non-blocking probe form is fine
        nonblocking = any(
            (kw.arg == "blocking" and isinstance(kw.value, ast.Constant)
             and kw.value.value is False)
            for kw in node.keywords) or any(
            isinstance(a, ast.Constant) and a.value is False
            for a in node.args[:1])
        if not nonblocking:
            return f"`{name}` is a blocking lock acquire"
        return None
    if last == "join" and recv is not None and _threadish(recv_name):
        return f"`{name}` joins a thread"
    if last == "get" and recv is not None and _queueish(recv_name) \
            and not node.args:
        # queue.Queue.get() blocks unless explicitly non-blocking;
        # positional args mean dict.get(key, ...) — not a queue
        nonblocking = any(
            (kw.arg == "block" and isinstance(kw.value, ast.Constant)
             and kw.value.value is False) or kw.arg == "timeout"
            for kw in node.keywords)
        if not nonblocking:
            return f"`{name}` can block forever on an empty queue"
        return None
    if last in RPC_CALLS and recv is not None:
        return f"RPC `{name}` rides the network"
    if last in STORAGE_OPS and recv is not None:
        return f"storage I/O `{name}` touches disk"
    if last in SUBPROCESS_CALLS and recv is not None \
            and recv_name in ("subprocess", "sp"):
        return f"`{name}` forks and waits on a child process"
    if last in SOCKET_CALLS and recv is not None and _sockish(recv_name):
        return f"socket op `{name}` rides the network"
    return None


# ---------------------------------------------------------------------------
# graph data model
# ---------------------------------------------------------------------------
class CallSite:
    """One call expression inside a function body (nested defs own
    their calls — see _walk_body)."""

    __slots__ = ("call", "lineno", "col", "name", "target", "hop",
                 "awaited")

    def __init__(self, call: ast.Call, name: str, target: str | None,
                 hop: bool, awaited: bool):
        self.call = call
        self.lineno = call.lineno
        self.col = call.col_offset
        self.name = name          # dotted-ish callee name for display
        self.target = target      # FuncNode key or None (unresolved)
        self.hop = hop            # runs on another thread/process
        self.awaited = awaited    # `await <call>` — loop-friendly

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        flags = "".join(f for f, on in (("H", self.hop),
                                        ("A", self.awaited)) if on)
        return f"{self.name}@{self.lineno}" \
               f"{'[' + flags + ']' if flags else ''}" \
               f" -> {self.target or '?'}"


class LockWith:
    """One `with <lockish>:` item: its resolved lock key, the with
    statement, and which call sites sit lexically inside the body."""

    __slots__ = ("node", "lock_src", "lock_key", "is_cond", "calls")

    def __init__(self, node: ast.With, lock_src: str,
                 lock_key: str, is_cond: bool):
        self.node = node
        self.lock_src = lock_src
        self.lock_key = lock_key
        self.is_cond = is_cond
        self.calls: list[CallSite] = []


class FuncNode:
    __slots__ = ("key", "module", "node", "cls", "is_async", "calls",
                 "lock_withs", "acquires")

    def __init__(self, key: str, module, node, cls, is_async: bool):
        self.key = key
        self.module = module      # core.Module
        self.node = node          # FunctionDef/AsyncFunctionDef/Lambda
        self.cls = cls            # _ClassInfo or None
        self.is_async = is_async
        self.calls: list[CallSite] = []
        #: lockish `with` regions, in source order
        self.lock_withs: list[LockWith] = []
        #: lock keys this function acquires DIRECTLY (withs + .acquire)
        self.acquires: list[tuple[str, int]] = []  # (lock key, lineno)


class _ClassInfo:
    __slots__ = ("name", "dotted", "bases", "methods", "attr_types")

    def __init__(self, name: str, dotted: str):
        self.name = name
        self.dotted = dotted           # owning module's dotted name
        self.bases: list[tuple[str, str]] = []   # (dotted, class name)
        self.methods: dict[str, str] = {}        # method -> FuncNode key
        self.attr_types: dict[str, tuple[str, str]] = {}  # self.x -> cls

    @property
    def key(self) -> str:
        return f"{self.dotted}.{self.name}"


def module_dotted(path: str) -> str:
    """Stable dotted id for a Module path: the part from the package
    root down ("minio_tpu.server.app"); fixture paths degrade to their
    own stem ("mod")."""
    parts = path.replace("\\", "/").split("/")
    if parts[-1].endswith(".py"):
        parts[-1] = parts[-1][:-3]
    if parts[-1] == "__init__":
        parts = parts[:-1]
    if "minio_tpu" in parts:
        parts = parts[parts.index("minio_tpu"):]
    return ".".join(p for p in parts if p) or "mod"


class CallGraph:
    """The package graph.  Build once per Project; query from rules."""

    #: traversal bound: deeper chains than this are noise, not findings
    MAX_DEPTH = 25

    def __init__(self, modules):
        self.nodes: dict[str, FuncNode] = {}
        self.classes: dict[str, _ClassInfo] = {}   # "dotted.Cls" -> info
        self.by_ast: dict[int, FuncNode] = {}      # id(func ast) -> node
        self._mod_by_dotted: dict[str, object] = {}
        self._imports: dict[str, dict] = {}        # dotted -> import map
        self._mod_funcs: dict[str, dict[str, str]] = {}
        self._mod_classes: dict[str, dict[str, str]] = {}
        self._blocking_memo: dict[str, tuple | None] = {}
        self._acquired_memo: dict[str, frozenset] = {}
        self._edges_memo: dict | None = None
        self._cycles_memo: list | None = None
        self._mro_memo: dict[str, list] = {}
        self._descendants: dict[str, list] | None = None
        self._build(modules)

    # ------------------------------------------------------------ build
    def _build(self, modules) -> None:
        for mod in modules:
            self._mod_by_dotted[module_dotted(mod.path)] = mod
        for mod in modules:
            self._index_module(mod)
        self._resolve_inheritance()
        self._infer_attr_types()
        for node in list(self.nodes.values()):
            self._link_function(node)

    def _index_module(self, mod) -> None:
        dotted = module_dotted(mod.path)
        imports: dict[str, tuple] = {}
        funcs: dict[str, str] = {}
        classes: dict[str, str] = {}
        self._imports[dotted] = imports
        self._mod_funcs[dotted] = funcs
        self._mod_classes[dotted] = classes

        for stmt in mod.tree.body:
            self._index_imports(stmt, dotted, imports)
        # lazy imports inside function bodies resolve too (the repo
        # defers heavy imports); last one wins, which is fine — the
        # package has one meaning per name
        for n in ast.walk(mod.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for stmt in n.body:
                    self._index_imports(stmt, dotted, imports)

        for stmt in mod.tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                key = self._add_func(mod, stmt, f"{dotted}.{stmt.name}",
                                     None)
                funcs[stmt.name] = key
            elif isinstance(stmt, ast.ClassDef):
                info = _ClassInfo(stmt.name, dotted)
                self.classes[info.key] = info
                classes[stmt.name] = info.key
                for sub in stmt.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        key = self._add_func(
                            mod, sub, f"{info.key}.{sub.name}", info)
                        info.methods[sub.name] = key

    def _index_imports(self, stmt, dotted: str, imports: dict) -> None:
        if isinstance(stmt, ast.Import):
            for alias in stmt.names:
                name = alias.asname or alias.name.split(".")[0]
                target = alias.name if alias.asname else \
                    alias.name.split(".")[0]
                imports[name] = ("module", target)
        elif isinstance(stmt, ast.ImportFrom):
            base = stmt.module or ""
            if stmt.level:
                pkg = dotted.split(".")[:-stmt.level] if stmt.level \
                    else dotted.split(".")
                base = ".".join(pkg + ([stmt.module] if stmt.module
                                       else []))
            for alias in stmt.names:
                if alias.name == "*":
                    continue
                name = alias.asname or alias.name
                full = f"{base}.{alias.name}" if base else alias.name
                if full in self._mod_by_dotted:
                    imports[name] = ("module", full)
                else:
                    imports[name] = ("symbol", base, alias.name)

    def _add_func(self, mod, node, key: str, cls) -> str:
        is_async = isinstance(node, ast.AsyncFunctionDef)
        fn = FuncNode(key, mod, node, cls, is_async)
        self.nodes[key] = fn
        self.by_ast[id(node)] = fn
        return key

    def _resolve_class_name(self, dotted: str, name: str):
        """A class NAME used in module `dotted` -> _ClassInfo or None
        (locally defined or imported from a scanned module)."""
        key = self._mod_classes.get(dotted, {}).get(name)
        if key is not None:
            return self.classes[key]
        imp = self._imports.get(dotted, {}).get(name)
        if imp is not None and imp[0] == "symbol":
            return self.classes.get(f"{imp[1]}.{imp[2]}")
        return None

    def _resolve_inheritance(self) -> None:
        for info in self.classes.values():
            mod = self._mod_by_dotted.get(info.dotted)
            if mod is None:
                continue
            for stmt in mod.tree.body:
                if isinstance(stmt, ast.ClassDef) \
                        and stmt.name == info.name:
                    for b in stmt.bases:
                        if isinstance(b, ast.Name):
                            base = self._resolve_class_name(
                                info.dotted, b.id)
                        elif isinstance(b, ast.Attribute) and \
                                isinstance(b.value, ast.Name):
                            imp = self._imports[info.dotted].get(
                                b.value.id)
                            base = self.classes.get(
                                f"{imp[1]}.{b.attr}") \
                                if imp and imp[0] == "module" else None
                        else:
                            base = None
                        if base is not None:
                            info.bases.append((base.dotted, base.name))

    def _mro(self, info: _ClassInfo) -> list[_ClassInfo]:
        """Bounded depth-first linearization — enough for the package's
        mixin-style single-level hierarchies."""
        hit = self._mro_memo.get(info.key)
        if hit is not None:
            return hit
        out, seen, stack = [], set(), [info]
        while stack:
            cur = stack.pop(0)
            if cur.key in seen:
                continue
            seen.add(cur.key)
            out.append(cur)
            for dotted, name in cur.bases:
                base = self.classes.get(f"{dotted}.{name}")
                if base is not None:
                    stack.append(base)
        self._mro_memo[info.key] = out
        return out

    def _method(self, info: _ClassInfo, name: str) -> str | None:
        for cls in self._mro(info):
            key = cls.methods.get(name)
            if key is not None:
                return key
        # mixin pattern (server/app.py): the method lives on the
        # CONCRETE class that mixes `info` in — `self` at runtime is
        # the derived class.  Resolve through descendants when they all
        # agree on one target; an ambiguous name stays unresolved.
        candidates = {key for sub in self._subclasses_of(info)
                      for key in [self._method_own_mro(sub, name)]
                      if key is not None}
        if len(candidates) == 1:
            return candidates.pop()
        return None

    def _method_own_mro(self, info: _ClassInfo, name: str) -> str | None:
        for cls in self._mro(info):
            key = cls.methods.get(name)
            if key is not None:
                return key
        return None

    def _subclasses_of(self, info: _ClassInfo) -> list[_ClassInfo]:
        if self._descendants is None:
            desc: dict[str, list] = {}
            for other in self.classes.values():
                for cls in self._mro(other):
                    if cls is not other:
                        desc.setdefault(cls.key, []).append(other)
            self._descendants = desc
        return self._descendants.get(info.key, [])

    def _attr_type(self, info: _ClassInfo, attr: str):
        """Pinned constructor type of `self.<attr>` seen from class
        `info`: own MRO first, then descendant-unique (mixins read
        attrs the concrete class constructs)."""
        for cls in self._mro(info):
            t = cls.attr_types.get(attr)
            if t is not None:
                return t
        found = set()
        for sub in self._subclasses_of(info):
            for cls in self._mro(sub):
                t = cls.attr_types.get(attr)
                if t is not None:
                    found.add(t)
                    break
        if len(found) == 1:
            return found.pop()
        return None

    def _infer_attr_types(self) -> None:
        """Pin `self.x = ClassName(...)` constructor assignments so
        `self.x.m()` resolves.  Only direct constructor calls count —
        parameters and factory returns stay untyped (blind spot)."""
        for fn in list(self.nodes.values()):
            info = fn.cls
            if info is None:
                continue
            for stmt in ast.walk(fn.node):
                if not (isinstance(stmt, ast.Assign)
                        and isinstance(stmt.value, ast.Call)):
                    continue
                target_cls = self._class_of_call(
                    module_dotted(fn.module.path), stmt.value)
                if target_cls is None:
                    continue
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Attribute) \
                            and isinstance(tgt.value, ast.Name) \
                            and tgt.value.id == "self":
                        info.attr_types.setdefault(
                            tgt.attr, (target_cls.dotted,
                                       target_cls.name))

    def _class_of_call(self, dotted: str, call: ast.Call):
        f = call.func
        if isinstance(f, ast.Name):
            return self._resolve_class_name(dotted, f.id)
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            imp = self._imports.get(dotted, {}).get(f.value.id)
            if imp is not None and imp[0] == "module":
                key = self._mod_classes.get(imp[1], {}).get(f.attr)
                return self.classes.get(key) if key else None
        return None

    # ----------------------------------------------------- linking calls
    def _link_function(self, fn: FuncNode) -> None:
        dotted = module_dotted(fn.module.path)
        locals_: dict[str, str] = {}      # nested def name -> key
        local_types: dict[str, tuple] = {}  # var -> (dotted, Cls)
        body = fn.node.body if not isinstance(fn.node, ast.Lambda) \
            else [ast.Expr(fn.node.body)]
        # nested defs become their own nodes first, so calls resolve
        for stmt in body:
            for sub in self._shallow_defs(stmt):
                key = f"{fn.key}.<locals>.{sub.name}"
                if key not in self.nodes:
                    self._add_func(fn.module, sub, key, fn.cls)
                locals_[sub.name] = key
                self._link_function(self.nodes[key])
        # local constructor assignments: `c = ClassName(...)`
        for stmt in body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Assign) \
                        and isinstance(sub.value, ast.Call):
                    cls = self._class_of_call(dotted, sub.value)
                    if cls is None:
                        continue
                    for tgt in sub.targets:
                        if isinstance(tgt, ast.Name):
                            local_types[tgt.id] = (cls.dotted, cls.name)
        self._walk_body(fn, body, dotted, locals_, local_types,
                        lock_stack=[])

    @staticmethod
    def _shallow_defs(stmt):
        """Function defs at any depth inside `stmt` that are NOT inside
        a deeper def — each def layer links its own children."""
        out, stack = [], [(stmt, False)]
        while stack:
            node, under_def = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if not under_def:
                    out.append(node)
                under_def = True
            for child in ast.iter_child_nodes(node):
                stack.append((child, under_def))
        return out

    def _lock_key(self, fn: FuncNode, ctx: ast.expr) -> str | None:
        """Stable identity for a lockish context expression (see module
        docstring); None when the terminal name is not lockish."""
        name = terminal_name(ctx)
        if not name or not is_lockish(name):
            return None
        dotted = module_dotted(fn.module.path)
        if isinstance(ctx, ast.Attribute):
            recv = ctx.value
            if isinstance(recv, ast.Name) and recv.id in ("self", "cls") \
                    and fn.cls is not None:
                return f"C:{fn.cls.key}.{name}"
            # `self.site._mu`: key by the pinned type of self.site when
            # known, else by the attribute path on the owning class
            if isinstance(recv, ast.Attribute) \
                    and isinstance(recv.value, ast.Name) \
                    and recv.value.id in ("self", "cls") \
                    and fn.cls is not None:
                t = self._attr_type(fn.cls, recv.attr)
                if t is not None:
                    return f"C:{t[0]}.{t[1]}.{name}"
                return f"C:{fn.cls.key}.{recv.attr}.{name}"
            return f"F:{fn.key}.{expr_source(ctx)}"
        if isinstance(ctx, ast.Name):
            # module-level lock?  (assigned at module top level)
            for stmt in fn.module.tree.body:
                if isinstance(stmt, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == ctx.id
                        for t in stmt.targets):
                    return f"M:{dotted}.{ctx.id}"
            return f"F:{fn.key}.{ctx.id}"
        return None

    def _walk_body(self, fn: FuncNode, stmts, dotted, locals_,
                   local_types, lock_stack) -> None:
        """Record call sites + lockish with-regions in source order,
        stopping at nested defs (they are separate nodes)."""
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(stmt, ast.With):
                opened: list[LockWith] = []
                for item in stmt.items:
                    self._visit_expr(fn, item.context_expr, dotted,
                                     locals_, local_types, lock_stack,
                                     awaited=False)
                    key = self._lock_key(fn, item.context_expr)
                    if key is None:
                        continue
                    lw = LockWith(stmt, expr_source(item.context_expr),
                                  key, is_condish(
                                      terminal_name(item.context_expr)))
                    fn.lock_withs.append(lw)
                    fn.acquires.append((key, stmt.lineno))
                    opened.append(lw)
                self._walk_body(fn, stmt.body, dotted, locals_,
                                local_types, lock_stack + opened)
                continue
            # any other statement: visit its expressions, recursing into
            # compound bodies via iter_child_nodes
            self._visit_stmt(fn, stmt, dotted, locals_, local_types,
                             lock_stack)

    def _visit_stmt(self, fn, stmt, dotted, locals_, local_types,
                    lock_stack) -> None:
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            if isinstance(child, ast.With):
                self._walk_body(fn, [child], dotted, locals_,
                                local_types, lock_stack)
            elif isinstance(child, ast.expr):
                self._visit_expr(fn, child, dotted, locals_, local_types,
                                 lock_stack, awaited=False)
            else:
                self._visit_stmt(fn, child, dotted, locals_, local_types,
                                 lock_stack)

    def _visit_expr(self, fn, expr, dotted, locals_, local_types,
                    lock_stack, awaited) -> None:
        if isinstance(expr, (ast.Lambda,)):
            return
        if isinstance(expr, ast.Await):
            self._visit_expr(fn, expr.value, dotted, locals_,
                             local_types, lock_stack, awaited=True)
            return
        if isinstance(expr, ast.Call):
            self._record_call(fn, expr, dotted, locals_, local_types,
                              lock_stack, awaited)
            hop = call_name(expr).rsplit(".", 1)[-1] in HOP_CALLS
            for arg in list(expr.args) + [kw.value for kw in
                                          expr.keywords]:
                if hop and self._callable_target(
                        fn, arg, dotted, locals_, local_types):
                    continue  # recorded as a hop edge by _record_call
                self._visit_expr(fn, arg, dotted, locals_, local_types,
                                 lock_stack, awaited=False)
            return
        for child in ast.iter_child_nodes(expr):
            if isinstance(child, ast.expr):
                self._visit_expr(fn, child, dotted, locals_,
                                 local_types, lock_stack, awaited=False)

    def _callable_target(self, fn, arg, dotted, locals_,
                         local_types) -> str | None:
        """Resolve a callable ARGUMENT (a hop's payload): a function
        reference, a bound method, or a lambda (which becomes its own
        node)."""
        if isinstance(arg, ast.Lambda):
            key = f"{fn.key}.<lambda@{arg.lineno}>"
            if key not in self.nodes:
                self._add_func(fn.module, arg, key, fn.cls)
                self._link_function(self.nodes[key])
            return key
        if isinstance(arg, (ast.Name, ast.Attribute)):
            return self._resolve_ref(fn, arg, dotted, locals_,
                                     local_types)
        return None

    def _resolve_ref(self, fn, ref, dotted, locals_, local_types):
        """Resolve a Name/Attribute REFERENCE to a function node key."""
        if isinstance(ref, ast.Name):
            if ref.id in locals_:
                return locals_[ref.id]
            key = self._mod_funcs.get(dotted, {}).get(ref.id)
            if key is not None:
                return key
            imp = self._imports.get(dotted, {}).get(ref.id)
            if imp is not None and imp[0] == "symbol":
                key = self._mod_funcs.get(imp[1], {}).get(imp[2])
                if key is not None:
                    return key
                ckey = self._mod_classes.get(imp[1], {}).get(imp[2])
                if ckey is not None:
                    return self._method(self.classes[ckey], "__init__")
            ckey = self._mod_classes.get(dotted, {}).get(ref.id)
            if ckey is not None:
                return self._method(self.classes[ckey], "__init__")
            return None
        if not isinstance(ref, ast.Attribute):
            return None
        recv, attr = ref.value, ref.attr
        if isinstance(recv, ast.Name):
            if recv.id in ("self", "cls") and fn.cls is not None:
                return self._method(fn.cls, attr)
            imp = self._imports.get(dotted, {}).get(recv.id)
            if imp is not None and imp[0] == "module":
                key = self._mod_funcs.get(imp[1], {}).get(attr)
                if key is not None:
                    return key
                ckey = self._mod_classes.get(imp[1], {}).get(attr)
                if ckey is not None:
                    return self._method(self.classes[ckey], "__init__")
                return None
            t = local_types.get(recv.id)
            if t is not None:
                info = self.classes.get(f"{t[0]}.{t[1]}")
                if info is not None:
                    return self._method(info, attr)
            info = self._resolve_class_name(dotted, recv.id)
            if info is not None:
                # ClassName.m(...) or ClassName(...) handled above
                return self._method(info, attr)
            return None
        # self.<a>.<m>() via the pinned attr type
        if isinstance(recv, ast.Attribute) \
                and isinstance(recv.value, ast.Name) \
                and recv.value.id in ("self", "cls") \
                and fn.cls is not None:
            t = self._attr_type(fn.cls, recv.attr)
            if t is not None:
                info = self.classes.get(f"{t[0]}.{t[1]}")
                if info is not None:
                    return self._method(info, attr)
        return None

    def _record_call(self, fn, call, dotted, locals_, local_types,
                     lock_stack, awaited) -> None:
        name = call_name(call)
        last = name.rsplit(".", 1)[-1]
        hop = last in HOP_CALLS
        target = None
        if hop:
            # the edge goes to the CALLABLE ARGUMENT — it runs on the
            # other side of the thread boundary
            args = list(call.args) + [kw.value for kw in call.keywords
                                      if kw.arg in ("target", "func",
                                                    "fn", None)]
            for arg in args:
                target = self._callable_target(fn, arg, dotted, locals_,
                                               local_types)
                if target is not None:
                    break
        else:
            target = self._resolve_ref(fn, call.func, dotted, locals_,
                                       local_types)
        site = CallSite(call, name or "<computed>", target, hop, awaited)
        fn.calls.append(site)
        for lw in lock_stack:
            lw.calls.append(site)
        if last == "acquire" and isinstance(call.func, ast.Attribute) \
                and is_lockish(terminal_name(call.func.value)):
            key = self._lock_key(fn, call.func.value)
            if key is not None:
                fn.acquires.append((key, call.lineno))

    # ------------------------------------------------------------ queries
    def node(self, key: str) -> FuncNode | None:
        return self.nodes.get(key)

    def find(self, needle: str) -> list[FuncNode]:
        """Nodes whose key contains/ends with `needle` (the --callgraph
        debug entry point)."""
        exact = [n for k, n in self.nodes.items()
                 if k == needle or k.endswith("." + needle)]
        if exact:
            return exact
        return [n for k, n in self.nodes.items() if needle in k]

    def site_blocking(self, fn: FuncNode, site: CallSite,
                      _depth: int = 0, _seen: frozenset = frozenset()):
        """(chain, why) if this call site can block the calling thread,
        else None.  Semantics: a hop runs elsewhere (safe); `await` of
        an async def or an unresolved awaitable parks the task (safe);
        but `await self._helper(...)` where _helper is a plain sync def
        runs the body INLINE before anything is awaited, so sync
        targets are traversed even under await."""
        if site.hop:
            return None
        target = self.nodes.get(site.target) if site.target else None
        if site.awaited:
            if target is None or target.is_async:
                return None
        else:
            why = classify_blocking(site.call)
            if why is not None:
                return ([(site.name, fn.module.path, site.lineno)], why)
        if target is None or target.is_async:
            # calling an async def without await just builds a coro —
            # a different bug, not a blocking one
            return None
        sub = self.blocking_summary(target.key, _depth + 1,
                                    _seen | {fn.key})
        if sub is not None:
            chain, why = sub
            return ([(site.name, fn.module.path, site.lineno)] + chain,
                    why)
        return None

    def blocking_summary(self, key: str, _depth: int = 0,
                         _seen: frozenset = frozenset()):
        """First blocking terminal reachable from `key` through non-hop
        edges, or None.  Returns (chain, why) where chain is
        [(callsite_name, module_path, lineno), ...] ending at the
        terminal call."""
        if key in self._blocking_memo:
            return self._blocking_memo[key]
        if _depth > self.MAX_DEPTH or key in _seen:
            return None
        fn = self.nodes.get(key)
        if fn is None:
            return None
        result = None
        for site in fn.calls:
            result = self.site_blocking(fn, site, _depth, _seen)
            if result is not None:
                break
        self._blocking_memo[key] = result
        return result

    def acquired_locks(self, key: str, _depth: int = 0,
                       _seen: frozenset = frozenset()) -> frozenset:
        """Lock keys `key` may acquire — direct plus transitive through
        non-hop resolved edges (bounded)."""
        memo = self._acquired_memo.get(key)
        if memo is not None:
            return memo
        if _depth > self.MAX_DEPTH or key in _seen:
            return frozenset()
        fn = self.nodes.get(key)
        if fn is None:
            return frozenset()
        out = {k for k, _ in fn.acquires}
        for site in fn.calls:
            if site.hop or site.target is None:
                continue
            out |= self.acquired_locks(site.target, _depth + 1,
                                       _seen | {key})
        result = frozenset(out)
        if not _seen:  # only memoize top-level computations (complete)
            self._acquired_memo[key] = result
        return result

    def lock_order_edges(self) -> dict:
        """The static lock-acquisition-order graph:
        {(held, acquired): [(module_path, lineno, via), ...]}.
        `via` names the function/call that witnesses the edge."""
        if self._edges_memo is not None:
            return self._edges_memo
        edges: dict[tuple, list] = {}

        def add(a: str, b: str, path: str, lineno: int, via: str):
            if a == b:
                return  # reentrancy / sibling instances: not an order
            edges.setdefault((a, b), []).append((path, lineno, via))

        for fn in self.nodes.values():
            # lexical nesting: `with A:` enclosing `with B:`
            for lw in fn.lock_withs:
                for other in fn.lock_withs:
                    if other is lw:
                        continue
                    if self._encloses(lw, other):
                        add(lw.lock_key, other.lock_key,
                            fn.module.path, other.node.lineno, fn.key)
            # multi-item `with a, b:` — same With node, source order
            by_node: dict[int, list[LockWith]] = {}
            for lw in fn.lock_withs:
                by_node.setdefault(id(lw.node), []).append(lw)
            for group in by_node.values():
                for i, a in enumerate(group):
                    for b in group[i + 1:]:
                        add(a.lock_key, b.lock_key, fn.module.path,
                            a.node.lineno, fn.key)
            # interprocedural: calls under a lock that acquire others
            for lw in fn.lock_withs:
                for site in lw.calls:
                    if site.hop or site.target is None:
                        continue
                    for acq in self.acquired_locks(site.target):
                        add(lw.lock_key, acq, fn.module.path,
                            site.lineno, site.name)
        for sites in edges.values():
            sites.sort()
        self._edges_memo = edges
        return edges

    @staticmethod
    def _encloses(outer: LockWith, inner: LockWith) -> bool:
        if outer.node is inner.node:
            return False
        for n in ast.walk(outer.node):
            if n is inner.node:
                return True
        return False

    def lock_cycles(self) -> list[list]:
        """Cycles in the lock-order graph: each is
        [(held, acquired, witness_site), ...] closing back on the first
        held key.  Deterministic order for stable reports."""
        if self._cycles_memo is not None:
            return self._cycles_memo
        edges = self.lock_order_edges()
        adj: dict[str, list[str]] = {}
        for (a, b) in edges:
            adj.setdefault(a, []).append(b)
        for targets in adj.values():
            targets.sort()
        cycles, seen_cycles = [], set()
        for start in sorted(adj):
            stack = [(start, [start])]
            while stack:
                cur, path = stack.pop()
                for nxt in adj.get(cur, ()):
                    if nxt == start and len(path) > 1:
                        canon = frozenset(path)
                        if canon in seen_cycles:
                            continue
                        seen_cycles.add(canon)
                        cyc = []
                        hops = path + [start]
                        for a, b in zip(hops, hops[1:]):
                            cyc.append((a, b, edges[(a, b)][0]))
                        cycles.append(cyc)
                    elif nxt not in path and len(path) < 8:
                        stack.append((nxt, path + [nxt]))
        self._cycles_memo = cycles
        return cycles

    # ---------------------------------------------------------- debug CLI
    def describe(self, needle: str) -> str:
        """Human-readable reachability dump for `--callgraph <fn>`:
        the node's color, edges, and any blocking chain — so a waiver
        review does not re-derive the chain by hand."""
        matches = self.find(needle)
        if not matches:
            return f"no node matches {needle!r}"
        out = []
        for fn in matches[:8]:
            color = "async (loop)" if fn.is_async else "sync"
            out.append(f"{fn.key}  [{color}]  "
                       f"{fn.module.path}:{fn.node.lineno}")
            for site in fn.calls:
                tag = " [hop]" if site.hop else \
                    (" [await]" if site.awaited else "")
                out.append(f"  line {site.lineno}: {site.name}"
                           f"{tag} -> {site.target or '<unresolved>'}")
            summary = self.blocking_summary(fn.key)
            if summary is not None:
                chain, why = summary
                out.append(f"  BLOCKING: {why}")
                for name, path, lineno in chain:
                    out.append(f"    via {name} at {path}:{lineno}")
            acq = sorted(self.acquired_locks(fn.key))
            if acq:
                out.append(f"  acquires: {', '.join(acq)}")
        return "\n".join(out)
