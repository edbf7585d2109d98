"""The command refuses what it cannot measure: a platform other than the
TPU, and a checkout that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from benchpath import ROOT

ARGS = ["--workload", "olmo-1b.chat-steady", "--seed", "1", "--seconds",
        "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_refuses_a_cpu_platform():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_a_checkout_of_the_benchmark_alone(tmp_path):
    """Without the program there is nothing to run, whatever the device:
    the run past its device check fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import json, sys; sys.path.insert(0, 'bench'); import run; "
            "print(json.dumps(run.run('olmo-1b.chat-steady', 1, 1.0, False, "
            "device_check=False, reduced=True)))")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "repro" in p.stderr
