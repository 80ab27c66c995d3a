"""AH: nothing reachable from the event loop may block it.

The critpath sampler (PR 7) MEASURES loop lag; this pass lists its
static causes.  A cross-module call graph is rooted at every
``async def`` in the configured roots plus every function passed BY
REFERENCE to a loop scheduler (``loop.call_soon``/``call_later``/
``call_at``/``call_soon_threadsafe``, ``Task.add_done_callback``) —
both run on the event loop thread.  The walk follows ordinary calls
(a sync helper called from a coroutine runs inline on the loop) and
resolves them across modules through imports, ``self.``/``cls.``
dispatch (including resolvable base classes) and module attributes.

The suspension-aware whitelist is structural: a function handed to
``asyncio.to_thread`` / ``loop.run_in_executor`` appears as an
*argument reference*, never as a call, so the executor hand-off points
fall out of the graph exactly where the loop stops running the code.
``AsyncHygieneConfig.boundary`` additionally names engine hand-off
functions (``"relpath::qualname"`` -> reason) the walk must not descend
into: their brief sync sections are a measured, justified budget.

Findings (all at the sink line, with one shortest witness chain):

AH101  blocking call (``time.sleep``, ``subprocess.run``, sync socket
       connect/resolve, ...) reachable from the loop
AH102  sync file IO (``open``, ``Path.read_text``/``write_bytes``...)
       reachable from the loop
AH103  sync lock acquisition (``.acquire()`` not awaited, or a plain
       ``with``-statement on a lock-named attribute) on the loop —
       the loop then waits on whatever thread holds the lock
AH104  three-argument ``pow`` on the loop: unbounded modular
       exponentiation (big-int crypto belongs behind the engine or an
       executor)
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..core import Finding, Pass, Project, attr_path, call_name, register_pass

_SCHEDULER_TAILS = {
    "call_soon",
    "call_later",
    "call_at",
    "call_soon_threadsafe",
    "add_done_callback",
}
_EXECUTOR_TAILS = {"to_thread", "run_in_executor"}


class _FuncInfo:
    __slots__ = ("relpath", "qualname", "node", "is_async", "cls")

    def __init__(self, relpath, qualname, node, is_async, cls):
        self.relpath = relpath
        self.qualname = qualname
        self.node = node
        self.is_async = is_async
        self.cls = cls  # enclosing class name, or None

    @property
    def key(self) -> Tuple[str, str]:
        return (self.relpath, self.qualname)


class _ModuleIndex:
    """Per-module name tables the cross-module resolver consults."""

    def __init__(self):
        self.toplevel: Dict[str, str] = {}  # name -> qualname (module fn)
        self.methods: Dict[str, Dict[str, str]] = {}  # class -> meth -> qual
        self.bases: Dict[str, List[str]] = {}  # class -> base name strings
        self.import_alias: Dict[str, str] = {}  # alias -> dotted module
        self.from_import: Dict[str, Tuple[str, str]] = {}  # name -> (mod, orig)


class _Graph:
    def __init__(self, project: Project, cfg):
        self.project = project
        self.cfg = cfg
        self.funcs: Dict[Tuple[str, str], _FuncInfo] = {}
        self.modules: Dict[str, _ModuleIndex] = {}
        self._module_path_cache: Dict[str, Optional[str]] = {}
        for relpath in project.python_files(cfg.roots):
            self._index_module(relpath)

    # -- indexing -----------------------------------------------------------

    def _index_module(self, relpath: str) -> None:
        tree = self.project.tree(relpath)
        idx = self.modules.setdefault(relpath, _ModuleIndex())

        def visit(body, qual: Sequence[str], cls: Optional[str]):
            for node in body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = ".".join(list(qual) + [node.name])
                    info = _FuncInfo(
                        relpath, q, node,
                        isinstance(node, ast.AsyncFunctionDef), cls,
                    )
                    self.funcs[info.key] = info
                    if not qual:
                        idx.toplevel[node.name] = q
                    elif cls is not None and len(qual) == 1:
                        idx.methods.setdefault(cls, {})[node.name] = q
                    visit(node.body, list(qual) + [node.name], cls)
                elif isinstance(node, ast.ClassDef):
                    if not qual:  # nested classes: out of scope
                        idx.bases[node.name] = [
                            ".".join(p) for p in map(attr_path, node.bases)
                            if p is not None
                        ]
                        visit(node.body, [node.name], node.name)
                elif isinstance(node, ast.Import):
                    for a in node.names:
                        if a.asname:
                            idx.import_alias[a.asname] = a.name
                        else:
                            head = a.name.split(".")[0]
                            idx.import_alias[head] = head
                elif isinstance(node, ast.ImportFrom):
                    mod = self._absolutize(relpath, node)
                    if mod is None:
                        continue
                    for a in node.names:
                        idx.from_import[a.asname or a.name] = (mod, a.name)
                elif isinstance(node, (ast.If, ast.Try)):
                    # TYPE_CHECKING / fallback-import blocks
                    visit(node.body, qual, cls)
                    for h in getattr(node, "handlers", []):
                        visit(h.body, qual, cls)
                    visit(node.orelse, qual, cls)

        visit(tree.body, [], None)

    @staticmethod
    def _absolutize(relpath: str, node: ast.ImportFrom) -> Optional[str]:
        if not node.level:
            return node.module
        parts = relpath.split("/")[:-1]  # package dirs of this module
        up = node.level - 1
        if up:
            parts = parts[:-up] if up <= len(parts) else []
        if node.module:
            parts = parts + node.module.split(".")
        return ".".join(parts) if parts else None

    def _module_relpath(self, dotted: str) -> Optional[str]:
        """Project-relative path of a dotted module, None if external."""
        hit = self._module_path_cache.get(dotted, "?")
        if hit != "?":
            return hit
        base = dotted.replace(".", "/")
        out = None
        for cand in (base + ".py", base + "/__init__.py"):
            if self.project.exists(cand):
                out = cand
                break
        self._module_path_cache[dotted] = out
        return out

    # -- resolution ---------------------------------------------------------

    def call_origin(self, relpath: str, cn: str) -> str:
        """Alias-resolved dotted origin of a call name ("" unknown).

        ``_time.sleep`` (import time as _time) and ``sleep`` (from time
        import sleep) both resolve to ``time.sleep``.
        """
        if not cn:
            return ""
        idx = self.modules.get(relpath)
        if idx is None:
            return cn
        parts = cn.split(".")
        if parts[0] in idx.import_alias:
            return ".".join([idx.import_alias[parts[0]]] + parts[1:])
        if parts[0] in idx.from_import:
            mod, orig = idx.from_import[parts[0]]
            return ".".join([mod, orig] + parts[1:])
        return cn

    def _resolve_in_module(
        self, relpath: str, name: str
    ) -> Optional[_FuncInfo]:
        idx = self.modules.get(relpath)
        if idx is None:
            return None
        q = idx.toplevel.get(name)
        if q is not None:
            return self.funcs.get((relpath, q))
        # a class: its constructor runs wherever it is called
        if name in idx.bases:
            init = idx.methods.get(name, {}).get("__init__")
            if init is not None:
                return self.funcs.get((relpath, init))
        if name in idx.from_import:
            mod, orig = idx.from_import[name]
            target = self._module_relpath(mod)
            if target is not None and target != relpath:
                return self._resolve_in_module(target, orig)
        return None

    def _resolve_method(
        self, relpath: str, cls: Optional[str], meth: str, seen: Set
    ) -> Optional[_FuncInfo]:
        if cls is None or (relpath, cls) in seen:
            return None
        seen.add((relpath, cls))
        idx = self.modules.get(relpath)
        if idx is None:
            return None
        q = idx.methods.get(cls, {}).get(meth)
        if q is not None:
            return self.funcs.get((relpath, q))
        for base in idx.bases.get(cls, []):
            head = base.split(".")[-1]
            # base in the same module
            hit = self._resolve_method(relpath, head, meth, seen)
            if hit is not None:
                return hit
            # base imported from a sibling module
            if head in idx.from_import:
                mod, orig = idx.from_import[head]
                target = self._module_relpath(mod)
                if target is not None:
                    hit = self._resolve_method(target, orig, meth, seen)
                    if hit is not None:
                        return hit
        return None

    def resolve_call(
        self, caller: _FuncInfo, cn: str
    ) -> Optional[_FuncInfo]:
        if not cn:
            return None
        relpath = caller.relpath
        parts = cn.split(".")
        if len(parts) == 1:
            # a def nested in the caller shadows everything outer
            nested = self.funcs.get((relpath, caller.qualname + "." + parts[0]))
            if nested is not None:
                return nested
            return self._resolve_in_module(relpath, parts[0])
        if parts[0] in ("self", "cls") and len(parts) == 2:
            return self._resolve_method(relpath, caller.cls, parts[1], set())
        idx = self.modules.get(relpath)
        if idx is None:
            return None
        # module-attribute call: resolve the module prefix, then the name
        if parts[0] in idx.import_alias or parts[0] in idx.from_import:
            origin = self.call_origin(relpath, cn)
            oparts = origin.split(".")
            for cut in range(len(oparts) - 1, 0, -1):
                target = self._module_relpath(".".join(oparts[:cut]))
                if target is None:
                    continue
                if cut == len(oparts) - 1:
                    return self._resolve_in_module(target, oparts[-1])
                if cut == len(oparts) - 2:
                    # Class.method on an imported class
                    return self._resolve_method(
                        target, oparts[-2], oparts[-1], set()
                    )
                return None
        return None

    def ref_target(
        self, caller: _FuncInfo, node: ast.AST
    ) -> Optional[_FuncInfo]:
        """A function REFERENCE (not call) in argument position."""
        path = attr_path(node)
        if path is None:
            return None
        return self.resolve_call(caller, ".".join(path))


def _own_statements(fn: ast.AST):
    """Walk a function body, NOT descending into nested defs/lambdas —
    those are separate graph nodes, on the loop only if actually called
    or referenced into a scheduler."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


@register_pass
class AsyncHygienePass(Pass):
    code_prefix = "AH"
    name = "async-hygiene"
    description = "no blocking sinks reachable from the event loop"
    scope = (
        "coroutine call graph over minbft_tpu/; sinks: "
        "blocking calls, sync file IO, sync lock acquire, 3-arg pow"
    )

    def run(self, project: Project) -> List[Finding]:
        cfg = getattr(project.config, "async_hygiene", None)
        if cfg is None:
            return []
        graph = _Graph(project, cfg)
        lock_re = re.compile(cfg.lock_attr_re)
        blocking = set(cfg.blocking_calls)
        io_calls = set(cfg.io_calls)
        io_methods = set(cfg.io_methods)
        boundary = set(cfg.boundary)

        # -- roots: async defs + loop-scheduled references ------------------
        roots: List[_FuncInfo] = [
            f for f in graph.funcs.values() if f.is_async
        ]
        for info in list(graph.funcs.values()):
            for node in _own_statements(info.node):
                if not isinstance(node, ast.Call):
                    continue
                cn = call_name(node)
                tail = cn.split(".")[-1] if cn else ""
                if tail in _SCHEDULER_TAILS:
                    for arg in node.args:
                        t = graph.ref_target(info, arg)
                        if t is not None:
                            roots.append(t)

        # -- reachability (BFS, parent pointers for the witness chain) ------
        parent: Dict[Tuple[str, str], Optional[Tuple[str, str]]] = {}
        queue: List[_FuncInfo] = []
        for r in roots:
            if r.key not in parent and self._bkey(r) not in boundary:
                parent[r.key] = None
                queue.append(r)
        edges_cache: Dict[Tuple[str, str], List[_FuncInfo]] = {}
        i = 0
        while i < len(queue):
            info = queue[i]
            i += 1
            callees = edges_cache.get(info.key)
            if callees is None:
                callees = self._callees(graph, info)
                edges_cache[info.key] = callees
            for c in callees:
                if c.key in parent or self._bkey(c) in boundary:
                    continue
                parent[c.key] = info.key
                queue.append(c)

        # -- sinks in every reachable function ------------------------------
        findings: List[Finding] = []
        for info in queue:
            chain = self._chain(parent, info.key)
            via = (
                f" [loop path: {' -> '.join(chain)}]"
                if len(chain) > 1
                else " [event-loop entry point]" if not info.is_async else ""
            )
            for node in _own_statements(info.node):
                findings.extend(
                    self._sinks_at(
                        graph, info, node, blocking, io_calls, io_methods,
                        lock_re, via,
                    )
                )
        return findings

    @staticmethod
    def _bkey(info: _FuncInfo) -> str:
        return f"{info.relpath}::{info.qualname}"

    @staticmethod
    def _chain(parent, key) -> List[str]:
        out = []
        while key is not None:
            out.append(key[1])
            key = parent[key]
        return list(reversed(out))

    def _callees(self, graph: _Graph, info: _FuncInfo) -> List[_FuncInfo]:
        out = []
        for node in _own_statements(info.node):
            if isinstance(node, ast.Call):
                cn = call_name(node)
                tail = cn.split(".")[-1] if cn else ""
                if tail in _EXECUTOR_TAILS:
                    continue  # args are executor-side: the whitelist
                t = graph.resolve_call(info, cn)
                if t is not None:
                    out.append(t)
        return out

    def _sinks_at(
        self, graph, info, node, blocking, io_calls, io_methods, lock_re, via
    ) -> List[Finding]:
        relpath = info.relpath
        out: List[Finding] = []
        if isinstance(node, ast.Call):
            cn = call_name(node)
            origin = graph.call_origin(relpath, cn)
            if origin in blocking:
                out.append(Finding(
                    "AH101", relpath, node.lineno,
                    f"blocking call {origin}() on the event loop in "
                    f"{info.qualname}{via}",
                ))
            elif origin in io_calls and graph.resolve_call(info, cn) is None:
                out.append(Finding(
                    "AH102", relpath, node.lineno,
                    f"sync file IO {origin}() on the event loop in "
                    f"{info.qualname}{via}",
                ))
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in io_methods
                and graph.resolve_call(info, cn) is None
            ):
                out.append(Finding(
                    "AH102", relpath, node.lineno,
                    f"sync file IO .{node.func.attr}() on the event loop "
                    f"in {info.qualname}{via}",
                ))
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "acquire"
                and isinstance(node.func.value, ast.Attribute)
                and lock_re.search(node.func.value.attr)
                and not self._is_awaited(info.node, node)
            ):
                out.append(Finding(
                    "AH103", relpath, node.lineno,
                    f"sync .acquire() on {node.func.value.attr} blocks the "
                    f"event loop in {info.qualname}{via}",
                ))
            elif cn == "pow" and len(node.args) == 3:
                out.append(Finding(
                    "AH104", relpath, node.lineno,
                    f"3-arg pow (modular exponentiation) on the event loop "
                    f"in {info.qualname}{via}",
                ))
        elif isinstance(node, ast.With):
            for item in node.items:
                path = attr_path(item.context_expr)
                if path and len(path) > 1 and lock_re.search(path[-1]):
                    out.append(Finding(
                        "AH103", relpath, node.lineno,
                        f"sync 'with {'.'.join(path)}' blocks the event "
                        f"loop in {info.qualname}{via}",
                    ))
        return out

    @staticmethod
    def _is_awaited(fn: ast.AST, call: ast.Call) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Await) and node.value is call:
                return True
        return False

    @classmethod
    def selftest(cls):
        from ..project import AnalyzeConfig, AsyncHygieneConfig

        files = {
            "app.py": (
                "import time\n"
                "def helper():\n"
                "    time.sleep(1)\n"
                "async def handler():\n"
                "    helper()\n"
            ),
        }
        config = AnalyzeConfig(
            source_roots=("app.py",), lock_classes=(), trace=None,
            exhaustiveness=None, secrets=None, dead=None,
            async_hygiene=AsyncHygieneConfig(roots=("app.py",)),
        )
        return files, config
