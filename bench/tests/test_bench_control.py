"""The harness end to end on tiny cells on the CPU, with its look for a
chip skipped: a sound run is correct; the control (the program's own
int8 matmul path, one precision below the bf16 served) and the faults of
``benchlib/faults.py`` planted in the timed path are not, each on the
number its cell compares (glm4 the widest gap, rwkv6 the mean gap); no
TPU, or no program beside the benchmark, exits non-zero with no result
line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
import run as bench_run  # noqa: E402
from benchlib.faults import FAULTS  # noqa: E402

SEED = 2 ** 31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(tmp_path_factory.mktemp("ctl"))


def result(capsys, root, cell, *extra, hook=None, rc=0):
    got = bench_run.main(["--workload", cell, "--seed", str(SEED),
                          "--seconds", "2", *extra], root=root,
                         require_tpu=False, compile_cache=False,
                         engine_hook=hook)
    out = capsys.readouterr()
    assert got == rc, out.err[-2000:]
    lines = [ln for ln in out.out.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def compared(out):
    """The one gap the cell compares: (value, limit)."""
    (name,) = set(out["checks"]) - {"unfinished"}
    return out["checks"][name]["value"], out["checks"][name]["limit"]


@pytest.mark.parametrize("cell,metric", [("tiny-glm4-chat", "ttft_p90_ms"),
                                         ("tiny-rwkv6-chat", "ttft_p90_ms"),
                                         ("tiny-glm4-prefix", "output_tok_s")])
def test_sound_run_is_correct(capsys, root, cell, metric):
    out = result(capsys, root, cell)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert {metric, "setup_s"} <= set(out["metrics"])
    assert list(out)[-1] == "checks"
    value, limit = compared(out)
    assert 0 <= value <= limit
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


@pytest.mark.parametrize("cell", ["tiny-glm4-chat", "tiny-rwkv6-chat"])
def test_control_is_not_correct(capsys, root, cell):
    out = result(capsys, root, cell, "--control")
    assert out["correct"] is False
    value, limit = compared(out)
    assert value > limit


@pytest.mark.parametrize("cell,fault,least", [
    ("tiny-glm4-chat", "token-altered", 1.0),
    ("tiny-glm4-chat", "state-unchanged", 1.0),
    ("tiny-rwkv6-chat", "state-unchanged", 0.1),
    ("tiny-glm4-prefix", "state-unchanged", 1.0)],
    ids=["token-altered", "glm4-state-unchanged", "rwkv6-state-unchanged",
         "paged-state-unchanged"])
def test_a_broken_timed_path_is_not_correct(capsys, root, cell, fault,
                                            least):
    out = result(capsys, root, cell, hook=FAULTS[fault])
    assert out["correct"] is False
    value, limit = compared(out)
    assert value > max(limit, least)


def test_no_tpu_exits_without_a_result(capsys, root):
    got = bench_run.main(["--workload", "tiny-glm4-chat", "--seed", "1",
                          "--seconds", "1"], root=root,
                         compile_cache=False)
    out = capsys.readouterr()
    assert got == 2 and "no TPU" in out.err
    assert not [ln for ln in out.out.splitlines() if ln.startswith("{")]


def test_benchmark_alone_exits_without_a_result(tmp_path):
    shutil.copytree(os.path.join(BENCH), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    with open(tmp_path / "BENCHMARK.json") as f:
        cell = json.load(f)["workloads"][0]["name"]
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        cell, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "not importable" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
