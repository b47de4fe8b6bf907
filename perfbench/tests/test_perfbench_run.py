"""The command refuses to run without what a run needs."""
import json
import shutil
import subprocess
import sys

from perfbench.lib.cell import ROOT

ARGS = ["--workload", "qwen1.5-0.5b.decode_closed", "--seed", "5",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "perfbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _printed_a_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except ValueError:
            pass
    return False


def test_no_tpu_no_result():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)
    assert "TPU" in p.stderr


def test_only_the_benchmark_files_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)
