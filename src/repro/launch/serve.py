"""Serving launcher: batched requests through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \
        --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import build_model
from repro.runtime.serve_loop import (GangServeEngine, Request, ServeConfig,
                                      ServeEngine)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gang", action="store_true",
                    help="use the old lockstep scheduler")
    ServeConfig.add_args(ap)           # the shared engine flag set
    args = ap.parse_args(argv)
    ServeConfig.check_args(ap, args, gang=args.gang)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    def make_engine(incarnation=0):
        # only the first incarnation carries the injected fault: the
        # respawn must run the trace to completion
        config = ServeConfig.from_args(args, incarnation=incarnation)
        if args.mesh_shards:
            from repro.runtime.mesh_serve import MeshServeEngine
            return MeshServeEngine(model, params, config)
        return ServeEngine(model, params, config)

    if args.gang:
        engine = GangServeEngine(model, params, max_batch=args.max_batch,
                                 max_seq=args.max_seq)
    else:
        engine = make_engine()
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        n = int(rng.integers(4, 24))
        if cfg.input_kind == "tokens":
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        else:
            prompt = rng.standard_normal((n, cfg.d_model)).astype(np.float32)
        reqs.append(Request(i, prompt, max_new_tokens=args.max_new))
    t0 = time.time()
    if args.kill_at_step is not None:
        from repro.runtime.supervisor import ServeSupervisor
        sup = ServeSupervisor(make_engine)
        done = sup.run(reqs)
        engine = sup.engine
        for h in sup.history:
            print(f"# chaos: restart {h.restart} restored step "
                  f"{h.restored_step}; resumed {h.resumed_rids}, "
                  f"replayed {h.replayed_rids}, recovered "
                  f"{h.recovered_rids}")
    else:
        done = engine.serve(reqs)
    dt = time.time() - t0
    for r in done:
        print(f"req {r.rid}: prompt {len(r.prompt)} toks -> "
              f"{list(r.output[:8])}{'...' if len(r.output) > 8 else ''} "
              f"({(r.done_at - r.submitted_at) * 1e3:.0f} ms)")
    tput = sum(len(r.output) for r in done) / dt
    print(f"# {engine.metrics['prefill_tokens']} prefill toks, "
          f"{engine.metrics['decode_tokens']} decode toks, "
          f"{tput:.1f} tok/s")
    if not args.gang:
        print(f"# queue wait {engine.metrics['queue_wait_s'] * 1e3:.0f}ms, "
              f"slot occupancy {engine.metrics['slot_occupancy']:.0%}")
    if args.paged:
        print(f"# paged: prefix hits "
              f"{engine.metrics['prefix_hit_tokens']:.0f} tok, peak "
              f"blocks {engine.metrics['peak_blocks']:.0f}")
    if args.mesh_shards:
        print(f"# mesh: {engine.n_shards} shards, loads "
              f"{engine.shard_loads()}, "
              f"{engine.metrics['async_prefills']:.0f} async prefills, "
              f"{engine.metrics['overlap_steps']:.0f} overlapped steps")
    if args.spec:
        print(f"# spec ({args.drafter or 'ngram'}): acceptance "
              f"{engine.metrics['spec_acceptance']:.0%}, "
              f"{engine.metrics['tokens_per_step']:.2f} tokens/step over "
              f"{engine.metrics['decode_steps']:.0f} steps, "
              f"k hist {dict(sorted(engine.metrics.spec_k_hist.items()))}")
        if args.drafter == "draft_model":
            print(f"# drafter tiers: {engine.metrics['model_drafts']:.0f} "
                  f"model, {engine.metrics['fallback_drafts']:.0f} "
                  f"fallback dispatches")
    if args.snapshot_dir:
        print(f"# snapshots: {engine.metrics['snapshots']:.0f} taken "
              f"({engine.metrics['snapshot_s'] * 1e3:.0f} ms total), "
              f"restore {engine.metrics['restore_s'] * 1e3:.0f} ms")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
