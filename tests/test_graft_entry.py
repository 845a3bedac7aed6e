"""Driver-contract regression tests for __graft_entry__.py.

Round 1 failed its MULTICHIP artifact because dryrun_multichip only forced the
virtual CPU mesh from the __main__ block; the driver imports the module and
calls the function directly, so the function itself must self-configure.
These tests exercise the exact driver call patterns in fresh subprocesses.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, extra_env=None, timeout=300):
    env = dict(os.environ)
    # simulate the driver: no JAX_PLATFORMS/XLA_FLAGS pre-set by our conftest
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_dryrun_multichip_driver_import():
    # the driver's pattern: import module, call function — nothing else
    r = _run("import __graft_entry__; __graft_entry__.dryrun_multichip(8)")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "dryrun_multichip ok" in r.stdout


def test_dryrun_multichip_after_backend_init():
    # caller already initialized a (wrong-sized) backend before calling us
    r = _run(
        "import jax\n"
        "jax.config.update('jax_platforms','cpu')\n"
        "jax.config.update('jax_num_cpu_devices', 1)\n"
        "assert jax.device_count() == 1\n"
        "import __graft_entry__\n"
        "__graft_entry__.dryrun_multichip(8)\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "dryrun_multichip ok" in r.stdout


def test_entry_single_chip_compiles():
    r = _run(
        "import jax\n"
        "jax.config.update('jax_platforms','cpu')\n"
        "import __graft_entry__\n"
        "fn, args = __graft_entry__.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "print('entry ok', out.shape)\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "entry ok" in r.stdout


def test_chip_smoke_cpu_tiny_mode():
    """chip_smoke.py end to end in its explicit small-CPU mode (named on the
    command line, never inferred): the training, kernel and serving legs at
    toy sizes, a last-line JSON that says ``cpu``. (That it REFUSES a CPU
    without the flag is tier-1: tests/test_chip_paths.py.)"""
    import json

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--cpu-tiny",
         "--out", os.path.join(REPO, "chip_smoke_out", "test")],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "mode": "cpu-tiny",
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    legs = [l for l in r.stdout.splitlines() if l.startswith("LEG ")]
    assert [l.split()[1] for l in legs] == ["A", "B", "C"]
