"""Metrics-registry lint (DC400-DC402).

Every metric name handed to ``Metrics`` (``.counter`` / ``.gauge`` /
``.observe`` / ``.timer`` / the read-side ``get_*`` / ``percentile``,
plus ``prometheus(extra_gauges={...})`` keys) must be declared once in
the central ``METRICS`` registry (``utils/metrics.py``) with a matching
kind. That kills name drift between emitters and the ``/metrics`` docs:
a typo'd counter shows up as DC400 at the emit site instead of as a
mysteriously flat graph.

* **DC400** — name used but not declared (or declared with another kind).
* **DC401** — registry entry never used by any scanned call site (dead
  doc — delete it or wire the emitter). Only reported when the scan
  includes the registry itself and at least one call site.
* **DC402** — registry entry violating prometheus naming rules: names
  must be ``snake_case``; counters must not end in ``_total`` /
  ``_count`` and summaries must not end in ``_total`` / ``_seconds`` (the
  exposition layer appends those suffixes itself; a counter OF seconds
  names its unit, ``engine_tick_seconds`` → ``..._seconds_total``).

Dynamic names: f-strings become ``*`` wildcard patterns and must match a
wildcard registry entry (``pool_batches_size_*``). A name computed some
other way needs ``# distcheck: metric(name_a, name_b)`` on the call line
enumerating what it can resolve to (a local single-assignment from a
string conditional is resolved automatically).
"""

from __future__ import annotations

import ast
import fnmatch
import re
from typing import Dict, List, Optional, Tuple

from .core import Finding, SourceFile, is_subset_scan, register

_EMITTERS = {
    "counter": "counter",
    "get_counter": "counter",
    "gauge": "gauge",
    "get_gauge": "gauge",
    "observe": "summary",
    "timer": "summary",
    "percentile": "summary",
}
_KINDS = ("counter", "gauge", "summary")
_NAME_OK = re.compile(r"^[a-z][a-z0-9_*]*$")
_BAD_SUFFIX = {
    "counter": ("_total", "_count"),
    "summary": ("_total", "_seconds"),
    "gauge": ("_total",),
}


def _metrics_receiver(func: ast.Attribute) -> bool:
    base = func.value
    if isinstance(base, ast.Name):
        return base.id in ("metrics", "m")
    if isinstance(base, ast.Attribute):
        return base.attr == "metrics"
    return False


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _fstring_pattern(node: ast.JoinedStr) -> str:
    parts = []
    for v in node.values:
        s = _const_str(v)
        parts.append(s if s is not None else "*")
    return "".join(parts)


def _local_str_values(fn_node, name: str) -> Optional[List[str]]:
    """Resolve a Name used as a metric name: single assignment in the
    enclosing function from a string constant / conditional of them."""
    assigns = []
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.Assign):
            for tgt in sub.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    assigns.append(sub.value)
    if len(assigns) != 1:
        return None
    v = assigns[0]
    if isinstance(v, ast.IfExp):
        a, b = _const_str(v.body), _const_str(v.orelse)
        if a is not None and b is not None:
            return [a, b]
    s = _const_str(v)
    return [s] if s is not None else None


def _registry_of(sf: SourceFile) -> Dict[str, Tuple[str, int]]:
    """{name: (kind, line)} from a module-level ``METRICS = {...}``."""
    out: Dict[str, Tuple[str, int]] = {}
    for node in sf.tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "METRICS"
            for t in node.targets
        ):
            continue
        if not isinstance(node.value, ast.Dict):
            continue
        for k, v in zip(node.value.keys, node.value.values):
            name = _const_str(k) if k is not None else None
            if name is None:
                continue
            kind = ""
            if isinstance(v, (ast.Tuple, ast.List)) and v.elts:
                kind = _const_str(v.elts[0]) or ""
            elif _const_str(v) is not None:
                kind = _const_str(v) or ""
            out[name] = (kind, k.lineno)
    return out


def _matches(pattern: str, registry: Dict[str, Tuple[str, int]]):
    """Registry entry matching a use-pattern (either side may hold '*')."""
    if pattern in registry:
        return pattern
    for key in registry:
        if "*" in key and fnmatch.fnmatchcase(pattern.replace("*", "x"), key):
            return key
        if "*" in pattern and fnmatch.fnmatchcase(key, pattern):
            return key
    return None


@register
def check(files: List[SourceFile]) -> List[Finding]:
    registry: Dict[str, Tuple[str, int]] = {}
    registry_file: Optional[SourceFile] = None
    for sf in files:
        reg = _registry_of(sf)
        if reg:
            registry.update(reg)
            registry_file = sf
    out: List[Finding] = []
    if registry_file is not None:
        for name, (kind, line) in sorted(registry.items()):
            if kind not in _KINDS:
                out.append(Finding(
                    "DC402", registry_file.path, line, f"METRICS.{name}",
                    f"registry entry '{name}' has kind '{kind}' — expected "
                    f"one of {', '.join(_KINDS)}",
                ))
                continue
            if not _NAME_OK.match(name):
                out.append(Finding(
                    "DC402", registry_file.path, line, f"METRICS.{name}",
                    f"registry entry '{name}' is not snake_case",
                ))
            if name.rstrip("*").endswith(_BAD_SUFFIX[kind]):
                out.append(Finding(
                    "DC402", registry_file.path, line, f"METRICS.{name}",
                    f"{kind} '{name}' must not carry a reserved prometheus "
                    "suffix — the exposition layer appends it",
                ))
    if not registry:
        return out  # nothing to check against (subset scan)

    used: Dict[str, int] = {}

    def _use(sf: SourceFile, line: int, pattern: str, kind: str, sym: str):
        key = _matches(pattern, registry)
        if key is None:
            out.append(Finding(
                "DC400", sf.path, line, sym,
                f"metric '{pattern}' ({kind}) is not declared in the "
                "METRICS registry — add it (or fix the name drift)",
            ))
            return
        used[key] = used.get(key, 0) + 1
        decl_kind = registry[key][0]
        if decl_kind in _KINDS and decl_kind != kind:
            out.append(Finding(
                "DC400", sf.path, line, sym,
                f"metric '{pattern}' is declared as a {decl_kind} but used "
                f"as a {kind}",
            ))

    any_call_site = False
    for sf in files:
        for fn_node in ast.walk(sf.tree):
            if not isinstance(
                fn_node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                continue
            for node in ast.walk(fn_node):
                if not isinstance(node, ast.Call):
                    continue
                if not isinstance(node.func, ast.Attribute):
                    continue
                attr = node.func.attr
                if attr == "prometheus":
                    for kw in node.keywords:
                        val = kw.value
                        if isinstance(val, ast.Name):
                            # e.g. gauges sampled on the event loop, render
                            # pushed to the executor — resolve the local.
                            assigns = [
                                s.value for s in ast.walk(fn_node)
                                if isinstance(s, ast.Assign) and any(
                                    isinstance(t, ast.Name)
                                    and t.id == val.id
                                    for t in s.targets
                                )
                            ]
                            if len(assigns) == 1:
                                val = assigns[0]
                        if kw.arg == "extra_gauges" and isinstance(
                            val, ast.Dict
                        ):
                            any_call_site = True
                            for k in val.keys:
                                s = _const_str(k) if k is not None else None
                                if s is not None:
                                    _use(sf, k.lineno, s, "gauge",
                                         f"extra_gauges.{s}")
                    continue
                kind = _EMITTERS.get(attr)
                if kind is None or not _metrics_receiver(node.func):
                    continue
                if not node.args:
                    continue
                any_call_site = True
                arg = node.args[0]
                sym = f"metrics.{attr}"
                declared = sf.ann.at(node.lineno, "metric")
                if declared is not None:
                    for nm in declared.split(","):
                        nm = nm.strip()
                        if nm:
                            _use(sf, node.lineno, nm, kind, sym)
                    continue
                s = _const_str(arg)
                if s is not None:
                    _use(sf, arg.lineno, s, kind, sym)
                elif isinstance(arg, ast.JoinedStr):
                    _use(sf, arg.lineno, _fstring_pattern(arg), kind, sym)
                elif isinstance(arg, ast.IfExp) and (
                    _const_str(arg.body) is not None
                    and _const_str(arg.orelse) is not None
                ):
                    _use(sf, arg.lineno, _const_str(arg.body), kind, sym)
                    _use(sf, arg.lineno, _const_str(arg.orelse), kind, sym)
                elif isinstance(arg, ast.Name):
                    vals = _local_str_values(fn_node, arg.id)
                    if vals:
                        for nm in vals:
                            _use(sf, arg.lineno, nm, kind, sym)
                    else:
                        out.append(Finding(
                            "DC400", sf.path, arg.lineno, sym,
                            f"metric name '{arg.id}' is not statically "
                            "resolvable — annotate the call with "
                            "# distcheck: metric(name, ...)",
                        ))
                else:
                    out.append(Finding(
                        "DC400", sf.path, arg.lineno, sym,
                        "metric name expression is not statically "
                        "resolvable — annotate the call with "
                        "# distcheck: metric(name, ...)",
                    ))

    # Dead-declaration evidence is "no scanned call site emits it" — on a
    # subset scan (--changed) the emitters are usually the files NOT in
    # the scan, so the closed-world check stays silent.
    if registry_file is not None and any_call_site and not is_subset_scan():
        for name, (kind, line) in sorted(registry.items()):
            if name not in used:
                out.append(Finding(
                    "DC401", registry_file.path, line, f"METRICS.{name}",
                    f"registry entry '{name}' is never emitted by any "
                    "scanned call site — dead declaration",
                ))
    return out
