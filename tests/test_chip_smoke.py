"""``chip_smoke.py`` on the CPU: the guard refuses to run without a TPU,
and each phase passes its own checks at a small size (the chip runs the
same code at full size)."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(smoke, phase, **sizes):
    phases, checks = smoke.Phases(jax.devices()[0]), smoke.Checks()
    phase(phases, checks, 0, **sizes)
    return checks.failed


def test_guard_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "'cpu'" in out.err


def test_library_phase_checks_pass(smoke):
    assert _run(smoke, smoke.library_phase, n=512, d=1024,
                permutations=49, pcoa_check_n=512) == []


def test_serve_phase_checks_pass(smoke):
    assert _run(smoke, smoke.serve_phase, n=64, d=32) == []


def test_multichip_phase_checks_pass():
    """On four host devices, in a child process: the device count is
    fixed when JAX starts."""
    code = ("import sys, jax; sys.path.insert(0, 'src'); "
            "import chip_smoke as s; "
            "c = s.Checks(); "
            "s.multichip_phase(s.Phases(jax.devices()[0]), c, 0, n=128, "
            "d=32, permutations=100); "
            "print(__import__('json').dumps(c.failed))")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == []
