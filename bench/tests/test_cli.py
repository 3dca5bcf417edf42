import os
import subprocess
import sys

from bench.tests.cases import ROOT


def test_cli_without_a_tpu_names_it_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload", "pokec.front",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""
