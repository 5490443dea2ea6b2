"""A new traffic mix, metric and cell are found by name, as new files
plus new entries, with no edit to the harness; and BENCHMARK.json keeps
to its shape."""
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from benchlib import traffic as TR  # noqa: E402
from benchlib.cells import Bench  # noqa: E402
from benchlib.record import Run  # noqa: E402


def test_new_mix_metric_and_cell_found_by_name(tmp_path):
    root = bench_tiny.tiny_root(tmp_path)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "traffic", "burst-chat.json"), "w") as f:
        json.dump({"kind": "open_loop", "rate_per_s": 3.0,
                   "prompt": {"dist": "uniform", "min": 8, "max": 8},
                   "output": {"dist": "uniform", "min": 4, "max": 4}}, f)
    with open(os.path.join(b, "metrics", "requests_seen.burst.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run.requests)\n")
    with open(os.path.join(b, "cells", "tiny-burst.json"), "w") as f:
        json.dump({"engine": ["--max-batch", "2", "--max-seq", "64"],
                   "traffic": {"rate_per_s": 5.0},
                   "limits": {"max_logit_gap": 0.5}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny-burst", "config": "tiny-glm4",
                              "traffic": "burst-chat", "chips": 1,
                              "why": "new"})
    spec["per_layer"].append({"name": "requests_seen.burst", "unit": "n",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "ttft_p90_ms",
                              "workloads": ["tiny-burst"]})
    for m in spec["end_to_end"]:
        if m["name"] == "ttft_p90_ms":
            m["workloads"].append("tiny-burst")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    bench = Bench(root)
    cell = bench.cell("tiny-burst")
    assert cell.traffic["rate_per_s"] == 5.0            # the cell's override
    assert cell.engine_flags[:2] == ["--max-batch", "2"]
    assert [m["name"] for m in cell.per_layer] == ["requests_seen.burst"]
    assert {m["name"] for m in cell.end_to_end} == {"ttft_p90_ms", "setup_s"}
    specs = TR.make(cell.traffic, 1, 2.0, 256)
    assert len(specs) == 10 and all(len(s.prompt) == 8 for s in specs)
    run = Run(cell="tiny-burst", terms={}, model={}, n_layers=1,
              max_batch=2, paged=False, seconds=2.0, t0=0.0, requests=[1, 2],
              step_walls=np.zeros(0), d0=0, counters={}, slot_occupancy=0.0,
              peaks={})
    assert bench.reader("requests_seen.burst")(run) == 2
    assert bench.reader("ttft_p90_ms") is bench.reader("ttft_p90_ms")


def test_benchmark_json_shape():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    bench = Bench(REPO)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(bench.path("metrics", f"{m['name']}.py"))
    for w in spec["workloads"]:
        cell = bench.cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
        assert cell.limits and set(cell.limits) <= {
            "max_logit_gap", "mean_logit_gap", "off_argmax_share"}
        assert os.path.exists(bench.path("reference",
                                         cell.config["reference"] + ".py"))
    for c in spec["configs"]:
        assert any(w["config"] == c["name"] for w in spec["workloads"])
    assert "TPU v5 lite" in json.load(open(bench.path("peaks.json")))[
        "devices"]
