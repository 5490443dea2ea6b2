"""Quickstart: the paper's CORDIC stack end to end in two minutes on CPU.

    PYTHONPATH=src python examples/quickstart.py

1. bit-exact 5-stage CORDIC MAC (Pallas kernel vs signed-digit oracle),
2. DA-VINCI activations vs exact,
3. a reduced glm4-family model trained for 30 steps under the paper's
   FxP8 execution policy, then served with batched requests.
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import CORDIC_EXEC, get_arch
from repro.core import fixed_point as fxp
from repro.core.activations import CordicPolicy, activate
from repro.data.pipeline import DataConfig, SyntheticStream
from repro.kernels.common import resolve_interpret
from repro.kernels.cordic_mac.kernel import cordic_matmul_raw
from repro.kernels.cordic_mac.ref import cordic_matmul_raw_ref
from repro.models.model_zoo import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime.serve_loop import Request, ServeEngine
from repro.runtime.train_loop import TrainConfig, Trainer


def main():
    rng = np.random.default_rng(0)

    print("== 1. CORDIC MAC kernel (SYCore dataflow, bit-exact) ==")
    fmt = fxp.FXP16
    x = fxp.quantize(jnp.array(rng.uniform(-2, 2, (32, 32)), jnp.float32), fmt)
    w = fxp.quantize(jnp.array(rng.uniform(-1.9, 1.9, (32, 32)), jnp.float32), fmt)
    got = cordic_matmul_raw(x, w, fmt=fmt, n_stages=5, block=(16, 16, 16),
                            interpret=resolve_interpret(None))
    want = cordic_matmul_raw_ref(x, w, fmt=fmt, n_stages=5)
    print("   kernel == signed-digit oracle:", bool((got == want).all()))

    print("== 2. DA-VINCI reconfigurable AFs ==")
    pol = CordicPolicy(bits=16)
    xs = jnp.linspace(-4, 4, 9)
    for af in ("tanh", "sigmoid", "gelu", "swish"):
        err = float(jnp.abs(activate(xs, af, pol) - activate(xs, af, None)).max())
        print(f"   {af:8s} max|err| = {err:.4f}")

    print("== 3. Train a reduced glm4 under the FxP8 policy ==")
    cfg = get_arch("glm4-9b").reduced()
    model = build_model(cfg)
    stream = SyntheticStream(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=32, global_batch=4, seed=0))
    trainer = Trainer(model, TrainConfig(
        optimizer=AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=30),
        log_every=10), stream, pol=CORDIC_EXEC)
    out = trainer.run(30)
    print("   loss:", " -> ".join(f"{l:.3f}" for _, l in out["losses"]))

    print("== 4. Serve batched requests ==")
    engine = ServeEngine(model, out["params"])
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                    max_new_tokens=4) for i in range(3)]
    for r in engine.serve(reqs):
        print(f"   req {r.rid}: -> {list(r.output)}")
    print("done.")


if __name__ == "__main__":
    main()
