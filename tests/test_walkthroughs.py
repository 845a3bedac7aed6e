"""Docs-as-tests for the narrative walkthroughs (VERDICT r3 next-#9): each
multi-stage walkthrough under docs/walkthroughs runs end to end — the
reference's executed-notebook tier (``docs/Explore Algorithms/`` +
``nbtest/DatabricksUtilities.scala``) as plain runnable scripts."""

import pathlib
import subprocess
import sys

import pytest

WALKTHROUGHS = sorted((pathlib.Path(__file__).parent.parent / "docs"
                       / "walkthroughs").glob("*.py"))


@pytest.mark.slow  # multi-stage: each trains + serves; full lane only
@pytest.mark.parametrize("walkthrough", WALKTHROUGHS, ids=lambda p: p.name)
def test_walkthrough_runs(walkthrough):
    # clean env like test_examples: the child sees only what is named here
    env = {"PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(walkthrough.parent.parent.parent),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run([sys.executable, str(walkthrough)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"{walkthrough.name} failed:\n{proc.stdout}\n{proc.stderr}")
