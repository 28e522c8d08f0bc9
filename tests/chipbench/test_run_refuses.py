"""`run.py` has no CPU fallback: without a TPU, or in a directory that
holds only the benchmark's own files, it exits non-zero and prints no
result line."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "bert_ae.s512_b32.1chip", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "benchmarks/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj and "metrics" in obj:
            out.append(obj)
    return out


def test_refuses_the_cpu():
    r = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert not _result_lines(r.stdout)
    assert "needs a TPU" in r.stderr


def test_refuses_a_set_pallas_switch():
    r = _run(ROOT, {"JAX_PLATFORMS": "cpu",
                    "FLEXFLOW_TPU_PALLAS": "interpret"})
    assert r.returncode != 0 and not _result_lines(r.stdout)
    assert "FLEXFLOW_TPU_PALLAS" in r.stderr


def test_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(ROOT, "tests", "chipbench"),
                    tmp_path / "tests" / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and not _result_lines(r.stdout)


def test_unknown_workload_is_an_error():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "benchmarks/run.py", "--workload",
                        "no_such_cell", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0 and "unknown workload" in r.stderr
