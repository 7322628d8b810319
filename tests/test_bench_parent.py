"""``bench.py``'s parent never holds a device: one process per chip.

The parent runs every phase as a child (``--phase NAME``); a parent that
had touched JAX would hold the chip and its children would fail or hang.
That is true by construction — everything above the ``__main__`` check is
standard library — and a failed phase is the run's failure."""

import ast
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")
_STDLIB = {"__future__", "json", "os", "subprocess", "sys", "time"}


def _parent_source():
    with open(BENCH) as f:
        src = f.read()
    tree = ast.parse(src)
    for i, node in enumerate(tree.body):
        if isinstance(node, ast.If) and "--phase" in ast.unparse(node.test):
            assert ast.unparse(node.body[0]) == "sys.exit(_parent())"
            return src, tree.body[:i], node.lineno
    raise AssertionError("bench.py lost its parent's early exit")


def test_parent_imports_only_the_standard_library():
    _, before, _ = _parent_source()
    imported = set()
    for node in ast.walk(ast.Module(body=before, type_ignores=[])):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= _STDLIB, imported - _STDLIB


def test_a_failed_phase_fails_the_run():
    """On this CPU a device phase's child fails (no tiny-model fallback):
    the parent — run here as bench.py's own top, in a clean interpreter —
    reports it, reruns nothing in-process, never imports jax, and exits
    non-zero."""
    src, _, lineno = _parent_source()
    snippet = (
        "import sys\n"
        f"top = ''.join(open({BENCH!r}).readlines()[:{lineno - 1}])\n"
        f"ns = {{'__file__': {BENCH!r}, '__name__': 'bench_parent'}}\n"
        f"exec(compile(top, {BENCH!r}, 'exec'), ns)\n"
        "ns['PHASE_NAMES'] = ('int8',)\n"
        "rc = ns['_parent']()\n"
        "assert 'jax' not in sys.modules\n"
        "sys.exit(rc)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", snippet], capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    failed = json.loads(proc.stdout.strip().splitlines()[-1])["failed_phases"]
    assert "rc=1" in failed["int8"]
    assert "tiny-cpu-fallback" not in src and '"isolation"' not in src
