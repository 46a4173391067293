"""The entry point refuses to measure anywhere but on a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import _paths  # noqa: F401
from yardstick import registry

RUN = Path(__file__).resolve().parents[1] / "run.py"


def test_no_tpu_exits_nonzero_and_prints_no_result():
    cell = registry.load_benchmark()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(RUN), "--workload", cell,
                        "--seed", str(2 ** 31 + 1), "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
