"""A checkout root holding the benchmark with tiny cells added, for the
tests that drive a whole run on the CPU.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``,
links the program's ``src/``, and adds two configurations at toy widths
(``tiny-glm4``, ``tiny-rwkv6``), a short chat mix and a short prefix
backlog, and three cells on them, as new files and new entries: the way
a later change adds a cell.
"""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {"n_layers": 2, "d_model": 128, "n_heads": 4, "head_dim": 32,
        "d_ff": 256, "vocab_size": 512, "norm_eps": 1e-05,
        "tie_embeddings": False}
CONFIGS = {
    "tiny-glm4": dict(arch="glm4-9b", reference="glm4", model=dict(
        TINY, n_kv_heads=2, rope_theta=10000.0, qkv_bias=True,
        activation="silu")),
    "tiny-rwkv6": dict(arch="rwkv6-3b", reference="rwkv6", model=dict(
        TINY, n_kv_heads=4, activation="relu")),
}
OUT = {"dist": "lognormal", "median": 40, "sigma": 0.5, "min": 8,
       "max": 64}
MIXES = {
    "tiny-chat": {"kind": "open_loop", "rate_per_s": 6.0,
                  "prompt": {"dist": "lognormal", "median": 20,
                             "sigma": 0.8, "min": 4, "max": 64},
                  "output": OUT},
    "tiny-prefix": {"kind": "backlog", "backlog": 64,
                    "prefix": {"count": 3, "tokens": 32, "zipf_s": 1.0},
                    "prompt": {"dist": "uniform", "min": 2, "max": 12},
                    "output": OUT},
}
E2E = {"tiny-chat": [("ttft_p90_ms", "ms", "lower"),
                     ("tpot_p90_ms", "ms", "lower")],
       "tiny-prefix": [("output_tok_s", "tokens/s", "higher")]}
ENGINE = ["--max-batch", "4", "--max-seq", "128"]
# What a tiny cell compares, as its full-size cell does: glm4 the widest
# reference gap (over 12 seeds on the CPU sound runs read at most 0.025,
# the int8 control at least 0.060 over 4), rwkv6 the mean gap (over 12
# seeds sound runs read at most 0.00030, the int8 control at least 0.00105
# over 4, a state left unchanged at least 1.1 over 2).
GLM4_LIMITS = {"max_logit_gap": 0.04}
RWKV6_LIMITS = {"mean_logit_gap": 0.0006}
CELLS = {
    "tiny-glm4-chat": ("tiny-glm4", "tiny-chat", ENGINE, GLM4_LIMITS),
    "tiny-rwkv6-chat": ("tiny-rwkv6", "tiny-chat", ENGINE, RWKV6_LIMITS),
    "tiny-glm4-prefix": ("tiny-glm4", "tiny-prefix", ENGINE + ["--paged"],
                         GLM4_LIMITS),
}


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_root(tmp: str) -> str:
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b = os.path.join(root, "bench")
    for name, conf in CONFIGS.items():
        _dump(os.path.join(b, "configs", f"{name}.json"),
              dict(conf, name=name))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "toy widths"})
    for name, mix in MIXES.items():
        _dump(os.path.join(b, "traffic", f"{name}.json"), mix)
    for name, (conf, mix, engine, limits) in CELLS.items():
        _dump(os.path.join(b, "cells", f"{name}.json"),
              {"engine": engine, "limits": limits})
        spec["workloads"].append({"name": name, "config": conf,
                                  "traffic": mix, "chips": 1,
                                  "why": "toy"})
        # report what the full-size cells of the same traffic report, and
        # at least the end-to-end metric of that traffic
        suffix = "-chat" if mix == "tiny-chat" else "-batch"
        have = {m["name"] for m in spec["end_to_end"]}
        for metric, unit, better in E2E[mix]:
            if metric not in have:
                spec["end_to_end"].append(
                    {"name": metric, "unit": unit, "better": better,
                     "bound": 0.25, "source": "host_clock", "workloads": []})
        own = {metric for metric, _, _ in E2E[mix]}
        for m in spec["end_to_end"] + spec["per_layer"]:
            if (any(w.endswith(suffix) for w in m.get("workloads", []))
                    or m["name"] in own):
                m["workloads"].append(name)
    # the CPU has no published peaks; the tests need some numbers to
    # exercise the arithmetic with, and no result of theirs is a speed
    peaks_path = os.path.join(b, "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    _dump(peaks_path, peaks)
    _dump(os.path.join(root, "BENCHMARK.json"), spec)
    return root
