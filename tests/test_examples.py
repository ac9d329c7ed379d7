"""Each example under ``examples/`` runs to its end at the size it ships
with. The examples assert their own answers against the BiBFS oracle (or
the paper's stated results), so a run that returns is a passing one."""
import os
import runpy
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(ROOT, "examples")

IN_PROCESS = ["quickstart", "fraud_detection", "online_service",
              "delta_updates", "sharded_service"]


@pytest.mark.parametrize("name", IN_PROCESS)
def test_example_runs(name, capsys):
    runpy.run_path(os.path.join(EXAMPLES, f"{name}.py"),
                   run_name="__main__")
    assert capsys.readouterr().out.strip()


def test_example_distributed_index_runs():
    """Sets ``XLA_FLAGS`` for an 8-device CPU mesh before importing jax,
    so it needs a process of its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "distributed_index.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "devices: 8" in out.stdout
    assert "oracle-verified" in out.stdout
