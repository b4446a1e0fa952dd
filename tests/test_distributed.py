"""Run multi-device scenarios in isolated subprocesses (each sets
XLA_FLAGS=--xla_force_host_platform_device_count=8 before importing jax,
per the dry-run isolation rule: the main pytest process stays 1-device)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")

SCRIPTS = ["mare_e2e.py", "moe_sharded.py", "grad_sync.py",
           "elastic_reshard.py", "dryrun_small.py", "ssm_cp.py",
           "ingest_waves.py", "keyed_skew.py", "stage_scopes.py"]


@pytest.mark.parametrize("script", SCRIPTS)
def test_distributed(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "distributed", script)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"{script} failed:\nSTDOUT:{proc.stdout}\nSTDERR:{proc.stderr[-4000:]}")
    assert "OK" in proc.stdout
