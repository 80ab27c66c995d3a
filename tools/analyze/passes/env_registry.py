"""ER: every environment knob is registered, described, and alive.

``MINBFT_*``/``CONSENSUS_*`` variables are the runtime's operator
surface; an undocumented knob is unusable and an undead registry entry
is a trap.  The pass collects every getenv-shaped site — any string
constant that IS a qualifying name (docstrings excluded; a name
embedded in prose never full-matches) plus f-string prefixes
(``f"MINBFT_FOO_{name}"`` -> ``MINBFT_FOO_*``) — and cross-checks
the committed registry ``tools/analyze/ENV_VARS.md``:

ER501  a live variable absent from the registry
ER502  a registry entry matching no live site (dead entry)
ER503  a registry entry whose description is empty or still TODO

``python -m tools.analyze --write-env-registry`` regenerates the file
from the live sites, preserving existing descriptions, so closing an
ER501 is one command plus one sentence.
"""

from __future__ import annotations

import ast
import re
from fnmatch import fnmatchcase
from typing import Dict, List, Set, Tuple

from ..core import Finding, Pass, Project, register_pass

_ENTRY_RE = re.compile(r"^\|\s*`(?P<name>[A-Z0-9_*]+)`\s*\|\s*(?P<desc>.*?)\s*\|\s*$")

_HEADER = """\
# Environment variable registry

Every `MINBFT_*`/`CONSENSUS_*` variable the runtime or the driver's
entry point reads — enforced by the `env-registry` analyzer pass
(ER501: unregistered, ER502: dead entry, ER503: missing description).
Regenerate with `python -m tools.analyze --write-env-registry`; the
command preserves descriptions, so only new rows need a sentence.

| Variable | Description |
|---|---|
"""


def _docstring_ids(tree: ast.Module) -> Set[int]:
    out: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(
            node,
            (ast.Module, ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
        ):
            body = node.body
            if (
                body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)
            ):
                out.add(id(body[0].value))
    return out


def collect_sites(project: Project, cfg) -> Dict[str, Tuple[str, int]]:
    """name-or-pattern -> (relpath, line) of the first site."""
    name_re = re.compile(cfg.name_re)
    prefix_re = re.compile(cfg.prefix_re)
    out: Dict[str, Tuple[str, int]] = {}
    for relpath in project.python_files(cfg.roots):
        tree = project.tree(relpath)
        skip = _docstring_ids(tree)
        for node in ast.walk(tree):
            if id(node) in skip:
                continue
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                if name_re.match(node.value):
                    out.setdefault(node.value, (relpath, node.lineno))
            elif isinstance(node, ast.JoinedStr):
                head = node.values[0] if node.values else None
                if (
                    isinstance(head, ast.Constant)
                    and isinstance(head.value, str)
                    and prefix_re.match(head.value)
                    and len(node.values) > 1
                ):
                    out.setdefault(
                        head.value + "*", (relpath, node.lineno)
                    )
    return out


def parse_registry(text: str) -> Dict[str, Tuple[str, int]]:
    """entry name/pattern -> (description, 1-based line)."""
    out: Dict[str, Tuple[str, int]] = {}
    for lineno, line in enumerate(text.splitlines(), start=1):
        m = _ENTRY_RE.match(line)
        if m and m.group("name") not in ("VARIABLE",):
            out.setdefault(m.group("name"), (m.group("desc"), lineno))
    return out


def _registered(name: str, entries: Dict[str, Tuple[str, int]]) -> bool:
    if name in entries:
        return True
    return any("*" in e and fnmatchcase(name, e) for e in entries)


def write_registry(project: Project) -> Tuple[str, int]:
    """Regenerate the registry from live sites, keeping descriptions."""
    cfg = project.config.env
    sites = collect_sites(project, cfg)
    path = project.root / cfg.registry
    old: Dict[str, Tuple[str, int]] = {}
    if path.is_file():
        old = parse_registry(path.read_text(encoding="utf-8"))
    rows = []
    for name in sorted(sites):
        desc = old.get(name, ("", 0))[0] or "TODO: describe"
        rows.append(f"| `{name}` | {desc} |")
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(_HEADER + "\n".join(rows) + "\n", encoding="utf-8")
    return cfg.registry, len(rows)


@register_pass
class EnvRegistryPass(Pass):
    code_prefix = "ER"
    name = "env-registry"
    description = "MINBFT_*/CONSENSUS_* knobs registered in ENV_VARS.md"
    scope = (
        "getenv sites in minbft_tpu/ + __graft_entry__.py vs "
        "tools/analyze/ENV_VARS.md"
    )

    def run(self, project: Project) -> List[Finding]:
        cfg = getattr(project.config, "env", None)
        if cfg is None:
            return []
        sites = collect_sites(project, cfg)
        findings: List[Finding] = []
        if not project.exists(cfg.registry):
            if sites:
                findings.append(Finding(
                    "ER501", cfg.registry, 1,
                    f"registry missing ({len(sites)} live variable(s) "
                    "unregistered) — run --write-env-registry",
                ))
            return findings
        entries = parse_registry(project.source(cfg.registry))
        for name, (relpath, line) in sorted(sites.items()):
            if not _registered(name, entries):
                findings.append(Finding(
                    "ER501", relpath, line,
                    f"env var {name} is read here but absent from "
                    f"{cfg.registry} — run --write-env-registry and "
                    "describe it",
                ))
        for entry, (desc, line) in sorted(entries.items()):
            alive = entry in sites or (
                "*" in entry
                and any(fnmatchcase(s, entry) for s in sites)
            ) or any(
                "*" in s and fnmatchcase(entry, s) for s in sites
            )
            if not alive:
                findings.append(Finding(
                    "ER502", cfg.registry, line,
                    f"registry entry {entry} matches no live getenv site — "
                    "dead entry, delete the row",
                ))
            elif not desc or desc.upper().startswith("TODO"):
                findings.append(Finding(
                    "ER503", cfg.registry, line,
                    f"registry entry {entry} has no description",
                ))
        return findings

    @classmethod
    def selftest(cls):
        from ..project import AnalyzeConfig, EnvRegistryConfig

        files = {
            "app.py": (
                "import os\n"
                'FLAG = os.environ.get("MINBFT_SELFTEST_FLAG")\n'
            ),
        }
        config = AnalyzeConfig(
            source_roots=("app.py",), lock_classes=(), trace=None,
            exhaustiveness=None, secrets=None, dead=None,
            env=EnvRegistryConfig(roots=("app.py",)),
        )
        return files, config
