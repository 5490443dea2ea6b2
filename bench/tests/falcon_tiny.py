"""A checkout root with a toy-width Falcon-H1 cell beside bench_tiny's:
``falcon_root(tmp)`` builds ``bench_tiny.tiny_root(tmp)`` and adds the
configuration ``tiny-falcon-h1`` (every structure of Falcon-H1's layer:
grouped-query attention beside Mamba-2 heads in two groups of two, a conv
of 4, the gated grouped norm, prompts of several SSD chunks) and the cell
``tiny-falcon-h1-chat`` on bench_tiny's chat mix, as new files and new
entries."""
from __future__ import annotations

import json
import os

import bench_tiny

# wider than bench_tiny's toys: at init, with the published multipliers,
# how much the output depends on the carried state grows with the widths
# (a state left unchanged moves the logits by 1.4% of their spread at
# d_model 128, by 8% at 512, against bf16's 0.6%); a vocabulary of 8192
# puts enough near-ties among the logits for a precision to show
MODEL = dict(bench_tiny.TINY, d_model=512, head_dim=128, n_kv_heads=2,
             d_ff=1024, vocab_size=8192, rope_theta=1e11,
             activation="silu", ssm_state=64,
             ssm_conv=4, ssm_heads=16, ssm_head_dim=128, ssm_groups=2,
             ssm_chunk=16, hybrid_fusion="sum")
CELL = "tiny-falcon-h1-chat"
# the widest gap, the one that separates at these widths.  Over 13 seeds
# on the CPU sound runs read at most 8.6e-5; the int8 control read 1.0e-4
# to 2.5e-4 on 11 of 13 and 5.9e-5 / 6.0e-5 on two, whose served tokens
# happened to meet few near-ties (teacher-forced, its logits' error is
# 3.6x a sound run's on every seed); RoPE skipped at least 3.2e-4, the KV
# heads swapped 5.8e-4, a state left unchanged 8.7e-4, over 4 each; a
# decode reading K/V one position off 3.4e-5 to 1.9e-4 over 3, writing
# it one early 3.8e-5 to 9.9e-5 over 4, as sound runs do.  The
# control's int8 activations take one scale for the whole batch, so its
# reading moves with which requests share a step: 1.12e-4 to 1.68e-4 at
# one seed
LIMITS = {"max_logit_gap": 1e-4}
DOC = ("ttft_p90_ms", "prefill_ms.chat", "decode_roofline.chat")


def falcon_root(tmp) -> str:
    root = bench_tiny.tiny_root(tmp)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny-falcon-h1.json"), "w") as f:
        json.dump({"name": "tiny-falcon-h1", "arch": "falcon-h1-34b",
                   "reference": "falcon_h1", "model": MODEL}, f)
    with open(os.path.join(b, "cells", f"{CELL}.json"), "w") as f:
        json.dump({"engine": bench_tiny.ENGINE, "limits": LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-falcon-h1", "source": "test",
                            "file": "bench/configs/tiny-falcon-h1.json",
                            "reduced": [], "why": "toy widths"})
    spec["workloads"].append({"name": CELL, "config": "tiny-falcon-h1",
                              "traffic": "tiny-chat", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in DOC and "workloads" in m:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(spec, f)
    return root
