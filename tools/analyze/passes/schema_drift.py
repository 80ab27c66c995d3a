"""SD: a Prometheus name a test pins must be one ``obs/prom.py`` registers.

The pass extracts, statically, the families registered in
``obs/prom.py`` (string constants and f-strings, placeholders normalized
to ``*``: ``f"minbft_{name}_total"`` -> ``minbft_*_total``) and the
``minbft_*`` string literals in the configured test files.

SD705  ``minbft_*`` name pinned in a test but registered by no prom
       family (exposition suffixes ``_bucket``/``_count``/``_sum``
       stripped before matching)
"""

from __future__ import annotations

import ast
import re
from fnmatch import fnmatchcase
from typing import List

from ..core import Finding, Pass, Project, register_pass

_PATTERN_RE = re.compile(r"^[a-z0-9_*]+$")
_EXPO_SUFFIXES = ("_bucket", "_count", "_sum")


def _norm_joined(node: ast.JoinedStr) -> str:
    parts = []
    for v in node.values:
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            parts.append(v.value)
        else:
            parts.append("*")
    return "".join(parts)


def _key_pattern(node: ast.AST) -> str:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        return _norm_joined(node)
    return ""


@register_pass
class SchemaDriftPass(Pass):
    code_prefix = "SD"
    name = "schema-drift"
    description = "prom names pinned in tests are registered"
    scope = "obs/prom.py families vs minbft_* names pinned in tests"

    def run(self, project: Project) -> List[Finding]:
        cfg = getattr(project.config, "schema", None)
        if cfg is None:
            return []
        # Analyzing a tree without the prom surface (--root on a
        # fixture/scratch checkout) is not drift — there is nothing to
        # cross-check.  The --selftest liveness gate keeps this from
        # silently disabling the pass on the real repo.
        if not project.exists(cfg.prom_module):
            return []
        findings: List[Finding] = []
        prom = self._prom_families(project, cfg)

        # SD705: test-pinned prom names must be registered
        for rel in cfg.pinned_tests:
            if not project.exists(rel):
                findings.append(Finding(
                    "SD705", rel, 1,
                    "configured pinned-test file does not exist",
                ))
                continue
            for node in ast.walk(project.tree(rel)):
                if not (
                    isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and re.fullmatch(r"minbft_[a-z0-9_]+", node.value)
                ):
                    continue
                name = node.value
                cands = [name] + [
                    name[: -len(s)]
                    for s in _EXPO_SUFFIXES
                    if name.endswith(s)
                ]
                if not any(
                    fnmatchcase(c, p) for c in cands for p in prom
                ):
                    findings.append(Finding(
                        "SD705", rel, node.lineno,
                        f"test pins prom name {name!r} but obs/prom.py "
                        "registers no matching family",
                    ))
        return findings

    # -- source extraction ---------------------------------------------------

    def _prom_families(self, project, cfg) -> List[str]:
        pats: List[str] = []
        for node in ast.walk(project.tree(cfg.prom_module)):
            pat = _key_pattern(node) if isinstance(
                node, (ast.Constant, ast.JoinedStr)
            ) else ""
            if not pat or not _PATTERN_RE.match(pat):
                continue
            if pat.startswith("minbft_") or (
                pat.startswith("*") and "_" in pat
            ):
                pats.append(pat)
        # exposition families: a histogram 'x' also exposes x_bucket/
        # x_count/x_sum; counters expose x alone — widen every family
        # with the exposition suffixes so pinned scrape-level names match
        pats += [p + s for p in list(pats) for s in _EXPO_SUFFIXES]
        return pats

    @classmethod
    def selftest(cls):
        from ..project import AnalyzeConfig, SchemaDriftConfig

        files = {
            "prom.py": "FAM = \"minbft_up\"\n",
            "test_pins.py": "BAD = \"minbft_never_registered_total\"\n",
        }
        # the pinned name matches no registered family -> SD705
        config = AnalyzeConfig(
            source_roots=("prom.py",), lock_classes=(), trace=None,
            exhaustiveness=None, secrets=None, dead=None,
            schema=SchemaDriftConfig(
                prom_module="prom.py",
                pinned_tests=("test_pins.py",),
            ),
        )
        return files, config
