#!/usr/bin/env python3
"""Prove the served path runs on a TPU: kernels, then glm4-9b through
``ServeEngine`` at its published widths.

    python3 chip_smoke.py [--seed N]        # one chip: kernels, dense, paged int8
    python3 chip_smoke.py --four-chips      # MeshServeEngine over 4 chips vs one

A smoke test, not a benchmark: the seconds it prints time one cold pass
(compiles included, or loaded from the persistent compilation cache) and
say nothing about steady-state speed.

Phases (one chip):

* kernels - each registered family's forward, compiled for the chip
  (``interpret=False``) at one main-path width, against its ``ref.py``:
  bit-exact for the fixed-point ``cordic_*`` families, allclose with the
  tolerance stated beside each float family.
* dense - glm4-9b with every published width and 8 of its 40 layers
  (40 layers are ~19 GB in bf16; 8 are ~5.8 GB and leave the rest of the
  chip's 16 GB to caches and activations), random weights from
  ``--seed``, served by ``ServeEngine.serve``: 8 greedy requests, prompts
  of 128..1024 tokens, 32 new tokens each, 8 slots of 2048 tokens.  Two
  requests are re-scored by ``transformer.forward`` in float32 under
  ``jax.default_matmul_precision("highest")``.
* paged int8 - the same requests through ``CacheSpec(dtype="int8",
  paged=True)`` with the radix prefix cache on, checked the same way.

``--four-chips`` runs only the sharded phase: the same requests through
``MeshServeEngine`` (slots sharded over 4 chips, weights replicated) and
through a one-device ``ServeEngine`` on chip 0; their greedy tokens must
match, and where they do not, both must pass the reference check.

Everything runs in this one process, which holds the chip(s) throughout
and starts no other.  The last stdout line is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``; without a
TPU, with ``REPRO_KERNEL_INTERPRET`` set, on any failed check, or
without the repository's ``src/`` beside it, it exits non-zero and does
not print it.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.configs.base import CacheSpec  # noqa: E402
from repro.core import fixed_point as fxp  # noqa: E402
from repro.kernels import common  # noqa: E402
from repro.kernels.cordic_softmax import ops as softmax_ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.model_zoo import build_model  # noqa: E402
from repro.runtime.mesh_serve import MeshServeEngine  # noqa: E402
from repro.runtime.serve_loop import (Request, ServeConfig,  # noqa: E402
                                      ServeEngine)

N_LAYERS = 8                      # of glm4-9b's 40; every width published
MAX_BATCH, MAX_SEQ = 8, 2048
PROMPT_LENS = (128, 256, 384, 512, 640, 768, 896, 1024)
NEW_TOKENS = 32
CHECKED = (0, len(PROMPT_LENS) - 1)     # shortest and longest prompt

# Reference margins, in units of the reference logit row's RMS (about 1
# for these random weights).  The engine picks the argmax of its own
# logits; if each logit it computes is within d of the reference, the
# token it emits has a reference logit within 2d of the reference max.
# The engine's logits are bf16 (8 significant bits): the top ones, at
# 4-8 RMS, are spaced 2**-5 apart, so rounding alone moves them up to
# 2**-6, and the residual stream is rounded to bf16 after every op of
# the 8 layers before that.  We budget d = 2**-4 RMS.  (Measured on a
# CPU at glm4-9b widths with 2 and 4 layers and a 16384-row vocabulary:
# bf16 logits differ from the float32 forward by 0.01 RMS on average
# and 0.073 RMS at most.)
MARGIN_BF16 = 2 * 2.0 ** -4
# The int8 cache stores each K/V vector as int8 with one scale, so an
# element is off by up to max|vector| / 254, twice bf16's worst error at
# that element (2**-9 of it); the attention read of every layer sees the
# coarser K/V, so d doubles:
MARGIN_INT8 = 2 * MARGIN_BF16


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading
    from the persistent cache) each jitted function, by name, and how
    many programs the persistent cache served."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        self.seconds = collections.Counter()
        self.total = 0.0
        self.cache = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:       # "f" when traced, "jit(f)" after
            name = kw.get("fun_name", "?")
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.seconds[name] += duration
            self.total += duration

    def _on_event(self, event, **kw):
        self.cache[event] += 1

    def take(self) -> dict:
        out, self.seconds = dict(self.seconds), collections.Counter()
        return out

    def summary(self) -> str:
        return (f"compile {self.total:.2f} s in all; "
                f"{self.cache[self.CACHE_HIT]} of "
                f"{self.cache[self.CACHE_ASKED]} programs loaded from the "
                f"persistent cache")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_cases(rng, widths=None):
    """(family, kernel kwargs, ref kwargs, args, bit-exact?, rtol, atol as
    a fraction of the largest ref output) at one main-path width each:
    glm4-9b's d_ff and heads, rwkv6-3b's heads."""
    w = dict(act=(256, 13696), softmax=(256, 4096), mac=(256, 4096, 512),
             flash=(32, 2, 2048, 128), wkv=(40, 1024, 64))
    w.update(widths or {})
    fmt = fxp.FXP16
    cases = []

    act = fxp.quantize(jnp.asarray(rng.uniform(-6, 6, w["act"]),
                                   jnp.float32), fmt)
    blk = common.pick_block_2d("cordic_act.tanh", w["act"])
    cases.append(("cordic_act", dict(af="tanh", fmt=fmt, block=blk),
                  dict(af="tanh", fmt=fmt), (act,), True, 0, 0))

    sm = fxp.quantize(jnp.asarray(rng.normal(size=w["softmax"]) * 2 - 3,
                                  jnp.float32), fmt)
    cases.append(("cordic_softmax",
                  dict(fmt=fmt, block_rows=softmax_ops.block_rows(sm.shape)),
                  dict(fmt=fmt), (sm,), True, 0, 0))

    m, k, n = w["mac"]
    x = fxp.quantize(jnp.asarray(rng.uniform(-2, 2, (m, k)), jnp.float32),
                     fmt)
    wt = fxp.quantize(jnp.asarray(rng.uniform(-1.9, 1.9, (k, n)),
                                  jnp.float32), fmt)
    cases.append(("cordic_mac",
                  dict(fmt=fmt, n_stages=5,
                       block=common.pick_block_matmul("cordic_mac", m, n, k)),
                  dict(fmt=fmt, n_stages=5), (x, wt), True, 0, 0))

    # bf16 q/k/v as in the model.  The output is rounded to bf16 (2**-9
    # relative) and the MXU may round the f32 probabilities to bf16 in
    # the PV product (2**-9 per term): allow 2**-7 of the largest output.
    hq, hkv, s, d = w["flash"]
    q, kk, v = (jnp.asarray(rng.normal(size=(h, s, d)), jnp.bfloat16)
                for h in (hq, hkv, hkv))
    g = hq // hkv
    cases.append(("flash_attention",
                  dict(causal=True, block_q=128, block_k=128, group=g),
                  dict(causal=True, group=g), (q, kk, v), False, 0,
                  2.0 ** -7))

    # f32 throughout: only summation order differs from the scan ref
    # (64-term dot products, 1024 sequential steps).  1e-4 of the largest
    # output is ~1600 f32 ulps, yet 20x tighter than bf16 arithmetic.
    bh, t, dk = w["wkv"]
    r, k_, v_ = (jnp.asarray(rng.normal(size=(bh, t, dk)), jnp.float32)
                 for _ in range(3))
    decay = jnp.asarray(rng.uniform(0.3, 1.0, (bh, t, dk)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(bh, dk)), jnp.float32)
    bt = common.pick_block_rows("wkv", (t, dk), jnp.float32, max_rows=64)
    cases.append(("wkv", dict(block_t=bt), {}, (r, k_, v_, decay, u), False,
                  1e-4, 1e-4))
    return cases


def kernel_phase(rng, clock, *, interpret=False, widths=None):
    failed = []
    for name, kkw, rkw, args, exact, rtol, atol in kernel_cases(rng, widths):
        spec = common.get_kernel(name)
        fn = jax.jit(lambda *a, s=spec, kw=kkw: s.kernel(
            *a, interpret=interpret, **kw))
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        got = np.asarray(jax.block_until_ready(compiled(*args)), np.float64)
        # the ref sees the same values in f32 (flash's bf16 inputs upcast)
        ref_args = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
                    for a in args]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(lambda *a, s=spec, kw=rkw: s.ref(
                *a, **kw))(*ref_args), np.float64)
        clock.take()
        shape = "x".join(str(a.shape) for a in args)
        if exact:
            bad = int((got != want).sum())
            ok = bad == 0
            verdict = f"bit-exact ({bad} of {want.size} differ)"
        else:
            err = float(np.abs(got - want).max())
            tol = atol * float(np.abs(want).max())
            ok = bool(np.all(np.abs(got - want)
                             <= tol + rtol * np.abs(want)))
            verdict = (f"allclose max|err| {err:.3g}, allowed {tol:.3g} "
                       f"+ {rtol:g}*|ref|")
        log(f"kernel {name} {shape}: {'PASS' if ok else 'FAIL'} "
            f"{verdict}; compile {compile_s:.2f} s")
        if not ok:
            failed.append(name)
    if failed:
        raise AssertionError(f"kernels disagree with their refs: {failed}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_requests(cfg, seed, prompt_lens=PROMPT_LENS, new=NEW_TOKENS):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=new)
            for i, n in enumerate(prompt_lens)]


def serve_config(extra=(), **overrides):
    """The ServeConfig ``launch/serve.py`` builds from its flags."""
    ap = argparse.ArgumentParser()
    ServeConfig.add_args(ap)
    args = ap.parse_args(["--max-batch", str(MAX_BATCH),
                          "--max-seq", str(MAX_SEQ), *extra])
    return ServeConfig.from_args(args, **overrides)


def serve(engine, reqs, clock, label):
    t0 = time.perf_counter()
    done = engine.serve(reqs)
    wall = time.perf_counter() - t0
    comp = clock.take()
    m = engine.metrics
    first = ("_extend_fn" if engine.paged else "_prefill_fn")
    log(f"{label} (smoke, not a benchmark): {m['prefill_tokens']} prefill "
        f"+ {m['decode_tokens']} decode tokens in {wall:.2f} s wall; first "
        f"prefill compile {comp.get(first, 0.0):.2f} s, first decode "
        f"compile {comp.get('_decode_fn', 0.0):.2f} s; prefix hits "
        f"{m['prefix_hit_tokens']} tokens")
    by_rid = {r.rid: r for r in done}
    for r in reqs:
        got = by_rid.get(r.rid)
        n = 0 if got is None or got.output is None else len(got.output)
        if got is None or got.status != "done" or n != r.max_new_tokens:
            raise AssertionError(f"{label}: request {r.rid} finished with "
                                 f"{n} of {r.max_new_tokens} tokens "
                                 f"({getattr(got, 'status', 'missing')})")
    return {rid: [int(t) for t in r.output] for rid, r in by_rid.items()}


class Reference:
    """float32 ``transformer.forward`` over prompt + output, highest
    matmul precision; the bf16 weights are the same values upcast."""

    def __init__(self, model, params):
        cfg = dataclasses.replace(model.cfg, dtype="float32")
        self.params = params
        self.fwd = jax.jit(lambda p, toks: T.forward(p, {"tokens": toks},
                                                     cfg))

    def check(self, reqs, outputs, margin, label):
        """Every emitted token's reference logit within ``margin`` * RMS
        of the reference row maximum."""
        # an f32 embedding puts the whole forward in f32; every other
        # weight is upcast inside its layer of the scan.  Made per check
        # so its 2.5 GB is not held while the engines serve.
        params = dict(self.params,
                      embed=self.params["embed"].astype(jnp.float32))
        width = max(len(r.prompt) for r in reqs) + NEW_TOKENS
        worst, argmax_hits, n = 0.0, 0, 0
        for i in range(0, len(reqs), 2):  # two rows of (width, vocab) f32
            pair = reqs[i:i + 2]
            toks = np.zeros((len(pair), width), np.int32)
            for j, r in enumerate(pair):  # causal: trailing pad is unseen
                row = np.concatenate([r.prompt, outputs[r.rid][:-1]])
                toks[j, :len(row)] = row
            with jax.default_matmul_precision("highest"):
                logits = self.fwd(params, toks)
            for j, r in enumerate(pair):
                p = len(r.prompt)
                out = outputs[r.rid]
                lg = np.asarray(logits[j, p - 1:p - 1 + len(out)],
                                np.float64)
                rms = np.sqrt((lg ** 2).mean(axis=-1))
                gap = (lg.max(-1) - lg[np.arange(len(out)), out]) / rms
                worst = max(worst, float(gap.max()))
                argmax_hits += int((gap == 0).sum())
                n += len(out)
        ok = worst <= margin
        log(f"{label} reference check: {'PASS' if ok else 'FAIL'} worst "
            f"gap {worst:.4f} RMS (margin {margin:.4f}); emitted token is "
            f"the reference argmax at {argmax_hits}/{n} steps")
        if not ok:
            raise AssertionError(f"{label}: an emitted token trails the "
                                 f"reference max by {worst:.4f} RMS > "
                                 f"{margin:.4f}")


def build_glm4(seed, layers=N_LAYERS, cfg=None, out_sharding=None):
    cfg = cfg or get_arch("glm4-9b").scaled(n_layers=layers)
    model = build_model(cfg)
    t0 = time.perf_counter()
    # one program draws every weight on the device (eagerly, each leaf
    # would compile its own)
    params = jax.block_until_ready(jax.jit(
        model.init, out_shardings=out_sharding)(jax.random.PRNGKey(seed)))
    log(f"{cfg.name}: {model.n_params() / 1e9:.3f} B params, {cfg.n_layers} "
        f"layers, d_model {cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} "
        f"heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; init "
        f"{time.perf_counter() - t0:.1f} s")
    return model, params


def serve_phases(seed, clock, model=None, params=None, **req_kw):
    if model is None:
        model, params = build_glm4(seed)
    ref = Reference(model, params)
    checked = lambda reqs: [reqs[i] for i in CHECKED]   # noqa: E731

    reqs = make_requests(model.cfg, seed, **req_kw)
    engine = ServeEngine(model, params, serve_config())
    dense = serve(engine, reqs, clock, "dense bf16 cache")
    del engine
    ref.check(checked(reqs), dense, MARGIN_BF16, "dense bf16 cache")

    reqs = make_requests(model.cfg, seed, **req_kw)
    engine = ServeEngine(model, params, serve_config(
        ["--paged"], cache=CacheSpec(dtype="int8", paged=True)))
    paged = serve(engine, reqs, clock, "paged int8 cache")
    del engine
    ref.check(checked(reqs), paged, MARGIN_INT8, "paged int8 cache")
    same = sum(a == b for rid in dense
               for a, b in zip(dense[rid], paged[rid]))
    log(f"paged int8 vs dense bf16: {same}/{sum(map(len, dense.values()))} "
        f"tokens equal (not required: int8 rounding may flip near-ties)")


def four_chip_phase(seed, clock, n_shards=4, cfg=None, **req_kw):
    devices = jax.devices()[:n_shards]
    mesh = make_mesh((n_shards,), ("data",), devices=devices)
    # replicated at birth: each chip holds one copy, and chip 0's shard
    # is the single-device engine's weights, not a second copy
    model, params = build_glm4(seed, cfg=cfg, out_sharding=NamedSharding(
        mesh, PartitionSpec()))
    reqs = make_requests(model.cfg, seed, **req_kw)
    engine = MeshServeEngine(model, params, serve_config(
        ["--mesh-shards", str(n_shards)]), mesh=mesh)
    sharded = serve(engine, reqs, clock, f"MeshServeEngine x{n_shards}")
    del engine

    def on_chip0(a):
        return next(s.data for s in a.addressable_shards
                    if s.device == devices[0])

    params0 = jax.tree.map(on_chip0, params)
    reqs1 = make_requests(model.cfg, seed, **req_kw)
    single = serve(ServeEngine(model, params0, serve_config()), reqs1,
                   clock, "ServeEngine on chip 0")
    diff = {rid: sum(a != b for a, b in zip(sharded[rid], single[rid]))
            for rid in single}
    if not any(diff.values()):
        log(f"four chips: greedy tokens match the one-chip engine for all "
            f"{len(single)} requests")
        return
    log(f"four chips: tokens differ from one chip in {diff}; both must "
        f"pass the reference check")
    ref = Reference(model, params0)
    ref.check(reqs, sharded, MARGIN_BF16, f"MeshServeEngine x{n_shards}")
    ref.check(reqs1, single, MARGIN_BF16, "ServeEngine on chip 0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the MeshServeEngine-on-4-chips phase")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_KERNEL_INTERPRET") is not None:
        print("REPRO_KERNEL_INTERPRET is set: refusing to run kernels "
              "other than compiled on the chip", file=sys.stderr)
        return 2

    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}; devices {devices}")
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {len(devices)}")
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"--four-chips needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    log(f"compilation cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed, clock)
    else:
        kernel_phase(np.random.default_rng(args.seed), clock)
        serve_phases(args.seed, clock)
    log(clock.summary())
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
