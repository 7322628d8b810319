"""JAX discipline checks (DC300, DC301).

**DC300 — PRNG key reuse.** A key variable (assigned from
``jax.random.PRNGKey`` / ``split`` / ``fold_in`` / ``key``) that is
consumed by a sampling primitive more than once without an intervening
re-derivation reuses randomness — two draws become correlated and the
byte-exact parity contract across serving paths silently breaks. Also
flagged: consuming a key inside a loop whose last derivation happened
outside the loop (every iteration draws the same stream). ``split`` and
``fold_in`` are derivations, not consumptions. Annotate deliberate reuse
(e.g. common random numbers in a test harness) with
``# distcheck: key-reuse-ok(reason)``.

**DC301 — host sync in the tick hot path.** Within engine tick-path
functions (``step`` and the ``_*tick`` / ``_*dispatch`` / ``_*resolve``
/ ``_*flush`` family under ``engine/``), ``jax.device_get`` (also
through the engine's ``self._fetch``, which times it as the tick's
``blocked`` phase) and ``.block_until_ready()`` force a device round-trip
per call. The tick
budget allows exactly the amortized fetches the overlap design
documents — each of those carries ``# distcheck: host-sync-ok(reason)``;
anything new gets flagged so the ragged-kernel work can't quietly grow
the per-tick sync count.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Tuple

from .core import Finding, SourceFile, call_name, register

_KEY_SOURCES = {
    "PRNGKey", "key", "split", "fold_in", "clone",
}
_DERIVE_FNS = {"split", "fold_in", "key", "PRNGKey", "clone", "wrap_key_data"}
_TICK_NAME = re.compile(
    r"^(step|_\w*(tick|dispatch|resolve|flush))$"
)


def _is_random_fn(name: str) -> Optional[str]:
    """'jax.random.categorical' -> 'categorical'; also 'random.foo' and
    bare re-exports like 'jrandom.foo'."""
    parts = name.split(".")
    if len(parts) >= 2 and parts[-2] in ("random", "jrandom"):
        return parts[-1]
    return None


class _KeyScan(ast.NodeVisitor):
    """Linear scan over one function: track per-variable key state.

    state[var] = (derive_line, loop_depth_at_derivation, consumed_count)
    """

    def __init__(self, sf: SourceFile, fn: str):
        self.sf = sf
        self.fn = fn
        self.state: Dict[str, Tuple[int, int, int]] = {}
        self.depth = 0
        self.out: List[Finding] = []

    def _assigned(self, tgt: ast.AST, from_key_source: bool) -> None:
        if isinstance(tgt, ast.Name):
            if from_key_source:
                self.state[tgt.id] = (tgt.lineno, self.depth, 0)
            else:
                self.state.pop(tgt.id, None)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for elt in tgt.elts:
                self._assigned(elt, from_key_source)

    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)
        is_key = False
        if isinstance(node.value, ast.Call):
            fn = _is_random_fn(call_name(node.value))
            is_key = fn in _DERIVE_FNS if fn else False
        for tgt in node.targets:
            self._assigned(tgt, is_key)

    def visit_Call(self, node: ast.Call) -> None:
        fn = _is_random_fn(call_name(node))
        if fn and fn not in _DERIVE_FNS:
            for arg in node.args[:1]:  # key is the first positional arg
                if isinstance(arg, ast.Name) and arg.id in self.state:
                    line0, depth0, count = self.state[arg.id]
                    ok = self.sf.ann.at(node.lineno, "key-reuse-ok")
                    if ok is None and (count >= 1 or depth0 < self.depth):
                        why = (
                            f"already consumed at line {line0}" if count >= 1
                            else f"derived outside this loop (line {line0})"
                        )
                        self.out.append(Finding(
                            "DC300", self.sf.path, node.lineno,
                            f"{self.fn}.{arg.id}",
                            f"PRNG key '{arg.id}' reused by "
                            f"jax.random.{fn} in {self.fn}() — {why}; "
                            "split/fold_in a fresh key per draw",
                        ))
                    self.state[arg.id] = (node.lineno, depth0, count + 1)
        self.generic_visit(node)

    def _loop(self, node) -> None:
        self.depth += 1
        self.generic_visit(node)
        self.depth -= 1

    visit_For = _loop
    visit_While = _loop

    def visit_FunctionDef(self, node):  # nested defs: separate unit
        pass

    visit_AsyncFunctionDef = visit_FunctionDef


def _host_sync_reason(node: ast.Call) -> Optional[str]:
    name = call_name(node)
    if name.endswith("device_get") and (
        name.startswith("jax") or name == "device_get"
    ):
        return "jax.device_get"
    if isinstance(node.func, ast.Attribute) and (
        node.func.attr == "block_until_ready"
    ):
        return ".block_until_ready()"
    if name == "jax.block_until_ready":
        return "jax.block_until_ready"
    if name == "self._fetch":
        # the engine's one road to jax.device_get on the tick path
        return "self._fetch"
    return None


def _check_tick(sf: SourceFile, node) -> List[Finding]:
    out: List[Finding] = []
    for sub in ast.walk(node):
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if sub is not node:
                continue
        if not isinstance(sub, ast.Call):
            continue
        reason = _host_sync_reason(sub)
        if reason is None:
            continue
        if sf.ann.at(sub.lineno, "host-sync-ok") is not None:
            continue
        out.append(Finding(
            "DC301", sf.path, sub.lineno, f"{node.name}:{reason}",
            f"host sync ({reason}) inside tick-path {node.name}() — each "
            "call stalls the decode tick on a device round-trip; batch it "
            "into the existing fetch or annotate host-sync-ok(reason)",
        ))
    return out


@register
def check(files: List[SourceFile]) -> List[Finding]:
    out: List[Finding] = []
    for sf in files:
        in_engine = "engine" in sf.path.split("/")[:-1] or (
            "fixtures" in sf.path
        )
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            scan = _KeyScan(sf, node.name)
            for a in list(node.args.args) + list(node.args.kwonlyargs):
                # Parameters named like keys are tracked from entry.
                if a.arg == "key" or a.arg.endswith(("_key", "rng")):
                    scan.state[a.arg] = (node.lineno, 0, 0)
            for stmt in node.body:
                scan.visit(stmt)
            out.extend(scan.out)
            if in_engine and _TICK_NAME.match(node.name):
                out.extend(_check_tick(sf, node))
    return out
