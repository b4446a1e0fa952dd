"""Run ``bench/run.py`` in a child process on the CPU, for the tests."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run(*args: str, script: Path = ROOT / "bench" / "run.py",
        timeout: float = 300) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script), *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


def result(proc: subprocess.CompletedProcess) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def cell_args(cell: str, seed: int = 2 ** 31 + 5, trace: int = 0):
    return ("--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace), "--rehearse")
