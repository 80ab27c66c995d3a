"""TL: every background task must be held, awaited, or callback'd.

``asyncio`` keeps only a WEAK reference to running tasks: a
``create_task`` result nobody retains can be garbage-collected
mid-flight and its exception silently dropped — the bug this codebase
fixed twice (PR 2, PR 6) before converging on the ``_bg_tasks``
contract (``self._bg_tasks.add(task)`` +
``task.add_done_callback(self._bg_tasks.discard)``).

TL601  a ``create_task``/``ensure_future`` result that is neither
       awaited, returned/yielded, stored (attribute, container,
       retainer-method argument), passed onward, nor given a
       ``add_done_callback`` — fire-and-forget, GC-able mid-flight
TL602  a tracked task collection iterated directly while its own
       done-callbacks mutate it (``add_done_callback(X.discard)``
       elsewhere in the class): a task finishing during the loop
       mutates the set under the iterator — snapshot with ``list()``
       first (the cancellation-leak pattern)
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from ..core import Finding, Pass, Project, attr_path, register_pass

_SNAPSHOTS = {"list", "tuple", "set", "frozenset", "sorted"}
_MUTATORS = {"discard", "remove", "pop"}


def _is_factory_call(node: ast.Call, factories) -> bool:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr in factories
    if isinstance(f, ast.Name):
        return f.id in factories
    return False


def _contains_name(node: ast.AST, name: str) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id == name for n in ast.walk(node)
    )


def _parents(tree: ast.AST) -> Dict[int, ast.AST]:
    out: Dict[int, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            out[id(child)] = node
    return out


def _enclosing_function(parents, node) -> Optional[ast.AST]:
    cur = parents.get(id(node))
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur
        cur = parents.get(id(cur))
    return None


@register_pass
class TaskLifecyclePass(Pass):
    code_prefix = "TL"
    name = "task-lifecycle"
    description = "background tasks are retained; tracked sets iterated safely"
    scope = (
        "create_task/ensure_future sites in minbft_tpu/; "
        "tracked-set iteration vs done-callback mutation"
    )

    def run(self, project: Project) -> List[Finding]:
        cfg = getattr(project.config, "tasks", None)
        if cfg is None:
            return []
        findings: List[Finding] = []
        for relpath in project.python_files(cfg.roots):
            findings.extend(self._check_module(project, cfg, relpath))
        return findings

    def _check_module(self, project, cfg, relpath: str) -> List[Finding]:
        tree = project.tree(relpath)
        parents = _parents(tree)
        findings: List[Finding] = []

        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _is_factory_call(
                node, cfg.factories
            ):
                findings.extend(
                    self._check_factory(parents, relpath, node, cfg)
                )

        # TL602: per-class (module-level defs count as one scope), find
        # collections whose done-callbacks self-mutate, then direct
        # iterations over them.
        scopes: List[ast.AST] = [tree] + [
            n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)
        ]
        for scope in scopes:
            findings.extend(self._check_iteration(relpath, scope, parents))
        return findings

    # -- TL601 --------------------------------------------------------------

    def _check_factory(self, parents, relpath, call, cfg) -> List[Finding]:
        factory = (
            call.func.attr
            if isinstance(call.func, ast.Attribute)
            else call.func.id
        )
        parent = parents.get(id(call))
        # await create_task(...) / await ensure_future(...): retained
        if isinstance(parent, ast.Await):
            return []
        msg = (
            f"{factory}() result is dropped — the task is GC-able "
            "mid-flight; hold it (the _bg_tasks pattern), await it, or "
            "add_done_callback"
        )
        # bare-expression statement: the result is discarded outright
        if isinstance(parent, ast.Expr):
            return [Finding("TL601", relpath, call.lineno, msg)]
        # value in a conditional expression: judge the IfExp's own
        # context (statement -> dropped; assignment -> track the name)
        if isinstance(parent, ast.IfExp):
            grand = parents.get(id(parent))
            if isinstance(grand, ast.Expr):
                return [Finding("TL601", relpath, call.lineno, msg)]
            parent = grand
        # assigned to a plain local name: the name must show evidence of
        # retention somewhere in the enclosing function
        name = None
        if isinstance(parent, (ast.Assign, ast.AnnAssign)):
            targets = (
                parent.targets
                if isinstance(parent, ast.Assign)
                else [parent.target]
            )
            if len(targets) == 1 and isinstance(targets[0], ast.Name):
                name = targets[0].id
            else:
                return []  # stored into an attribute/container: retained
        elif isinstance(parent, ast.NamedExpr):
            name = parent.target.id
        else:
            return []  # argument position, return value, etc.: retained
        fn = _enclosing_function(parents, call)
        scope = fn if fn is not None else parents.get(id(call))
        if scope is None or not self._name_retained(scope, call, name, cfg):
            return [Finding("TL601", relpath, call.lineno, msg)]
        return []

    @staticmethod
    def _name_retained(scope, factory_call, name, cfg) -> bool:
        for node in ast.walk(scope):
            if node is factory_call:
                continue
            if isinstance(node, ast.Await) and _contains_name(
                node.value, name
            ):
                return True
            if isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
                if node.value is not None and _contains_name(
                    node.value, name
                ):
                    return True
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                if node.value is not None and _contains_name(
                    node.value, name
                ):
                    return True
            if isinstance(node, ast.Call) and node is not factory_call:
                # t.add_done_callback(...): the loop's strong ref
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_done_callback"
                    and _contains_name(node.func.value, name)
                ):
                    return True
                # passed as an argument (gather, wait, tracked.add, ...)
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if _contains_name(arg, name):
                        return True
        return False

    # -- TL602 --------------------------------------------------------------

    @staticmethod
    def _scope_walk(scope):
        """Walk a TL602 scope without crossing into nested class scopes
        (each ClassDef is analyzed as its own scope)."""
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            if isinstance(node, ast.ClassDef):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    def _check_iteration(self, relpath, scope, parents) -> List[Finding]:
        # collection attr names a done-callback mutates in this scope
        mutated: Set[str] = set()
        for node in self._scope_walk(scope):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_done_callback"
            ):
                continue
            for arg in node.args:
                target = arg
                if isinstance(target, ast.Lambda):
                    # lambda t: self._tasks.discard(t)
                    body = target.body
                    if isinstance(body, ast.Call):
                        target = body.func
                if (
                    isinstance(target, ast.Attribute)
                    and target.attr in _MUTATORS
                    and isinstance(target.value, ast.Attribute)
                ):
                    mutated.add(target.value.attr)
        if not mutated:
            return []
        findings: List[Finding] = []
        for node in self._scope_walk(scope):
            iters: List[ast.AST] = []
            if isinstance(node, (ast.For, ast.AsyncFor)):
                iters.append(node.iter)
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp)):
                iters.extend(g.iter for g in node.generators)
            for it in iters:
                path = attr_path(it)
                if path and len(path) > 1 and path[-1] in mutated:
                    findings.append(Finding(
                        "TL602", relpath, node.lineno,
                        f"iterating {'.'.join(path)} directly while its "
                        "done-callbacks mutate it — a task finishing "
                        "mid-loop changes the set under the iterator; "
                        "snapshot with list(...) first",
                    ))
        return findings

    @classmethod
    def selftest(cls):
        from ..project import AnalyzeConfig, TaskLifecycleConfig

        files = {
            "app.py": (
                "import asyncio\n"
                "async def work():\n"
                "    pass\n"
                "async def go():\n"
                "    asyncio.create_task(work())\n"
            ),
        }
        config = AnalyzeConfig(
            source_roots=("app.py",), lock_classes=(), trace=None,
            exhaustiveness=None, secrets=None, dead=None,
            tasks=TaskLifecycleConfig(roots=("app.py",)),
        )
        return files, config
