"""Distributed tests on a small virtual mesh (subprocess with 8 host
devices): collectives correctness, MoE shard_map equivalence, sharding
rule engine, and a reduced-mesh dry-run of every family."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    # Hermeticity: conftest pins REPRO_TUNE_CACHE for *this* process, but
    # when pytest runs without the fixture env (or a dev shell exports a
    # real table) the subprocess would inherit — and autotune paths could
    # write — the user's persistent tuned table.  Pin a fresh absent path
    # per call, and pin the interpret knob to the parent's resolved value
    # so subprocess kernels compile the same way the parent's would.
    env["REPRO_TUNE_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="repro-dist-tuned-"), "absent.json")
    env["REPRO_KERNEL_INTERPRET"] = os.environ.get(
        "REPRO_KERNEL_INTERPRET", "1")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestShardingRules:
    def test_divisibility_fallback(self):
        out = run_py("""
            import jax, json
            from repro.launch.mesh import make_mesh
            from repro.parallel.sharding import spec_for
            mesh = make_mesh((2, 4), ("data", "model"))
            specs = {
                # vocab divisible by model=4 -> sharded
                "embed": str(spec_for((1024, 64), ("vocab", "embed"), mesh)),
                # 6 kv heads not divisible by 4 -> replicated
                "kv": str(spec_for((64, 6), ("embed", "kv_heads"), mesh)),
                # batch over data
                "x": str(spec_for((8, 16, 64), ("batch", "seq", "embed"), mesh)),
            }
            print(json.dumps(specs))
        """)
        specs = json.loads(out)
        assert "model" in specs["embed"]
        assert "model" not in specs["kv"]
        assert "data" in specs["x"]

    def test_no_axis_reused_in_one_tensor(self):
        out = run_py("""
            import jax
            from repro.launch.mesh import make_mesh
            from repro.parallel.sharding import spec_for
            mesh = make_mesh((2, 4), ("data", "model"))
            ps = spec_for((8, 4, 64), ("experts", "expert_mlp", "embed"), mesh)
            flat = []
            for e in ps:
                if e is None: continue
                flat += list(e) if isinstance(e, tuple) else [e]
            assert len(flat) == len(set(flat)), ps
            print("ok")
        """)
        assert "ok" in out


class TestCollectives:
    def test_ring_allreduce_matches_sum(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.parallel.collectives import ring_allreduce
            mesh = make_mesh((8,), ("data",))
            x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
            got = ring_allreduce(x, mesh, "data")
            want = np.tile(np.asarray(x).sum(0), (8, 1))
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
            print("ok")
        """)
        assert "ok" in out

    def test_hierarchical_allreduce(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.parallel.collectives import hierarchical_allreduce
            mesh = make_mesh((2, 4), ("pod", "data"))
            x = jnp.arange(2 * 4 * 8, dtype=jnp.float32).reshape(2, 4, 8)
            got = hierarchical_allreduce(x, mesh)
            want = np.broadcast_to(np.asarray(x).sum((0, 1)), (2, 4, 8))
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
            print("ok")
        """)
        assert "ok" in out


class TestMoEShardMap:
    def test_sharded_matches_local(self):
        """EP shard_map MoE == local dispatch (same routing, same weights)."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.configs import get_arch
            from repro.models import moe as M
            from repro.models.model_zoo import build_model
            cfg = get_arch("arctic-480b").reduced().scaled(
                n_experts=8, top_k=2, moe_d_ff=32, capacity_factor=4.0,
                dtype="float32")
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            bp = jax.tree_util.tree_map(lambda x: x[0],
                                        params["blocks"]["moe"])
            p = M.MoEParams(**bp)
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                                  jnp.float32)
            local, aux_l = M._moe_ffn_local(x, p, cfg, cfg.exec_policy)
            mesh = make_mesh((2, 4), ("data", "model"))
            with jax.set_mesh(mesh):
                shmap, aux_s = jax.jit(
                    lambda xx: M._moe_ffn_sharded(xx, p, cfg,
                                                  cfg.exec_policy, mesh))(x)
            err = float(jnp.abs(local - shmap).max())
            # capacity grouping differs (per-seq vs per-shard) => tiny drop
            # differences possible; with cf=4 nothing drops
            assert err < 1e-4, err
            print("ok", err)
        """)
        assert "ok" in out


class TestReducedMeshDryrun:
    @pytest.mark.parametrize("arch", ["glm4-9b", "arctic-480b", "rwkv6-3b",
                                      "hymba-1.5b"])
    def test_train_step_lowers_on_mesh(self, arch):
        """Reduced config, 2x4 mesh: train step lower+compile succeeds and
        SPMD partitions (collectives present for sharded params)."""
        out = run_py(f"""
            import jax, jax.numpy as jnp
            from repro.launch.mesh import make_mesh
            from repro.configs import get_arch
            from repro.models.model_zoo import build_model
            from repro.models import spec as pspec
            from repro.parallel import sharding as shd
            from repro.optim import adamw

            cfg = get_arch("{arch}").reduced()
            model = build_model(cfg)
            mesh = make_mesh((2, 4), ("data", "model"))
            p_sh = shd.tree_shardings(model.params_spec(), mesh)
            params_abs = model.abstract_params()
            batch_abs = model.input_specs(4, 32, "train")
            ocfg = adamw.AdamWConfig()
            opt_abs = jax.eval_shape(lambda: adamw.init(
                ocfg, pspec.abstract(model.params_spec())))

            def step(params, opt_state, batch):
                (l, m), g = jax.value_and_grad(
                    lambda p: model.loss(p, batch), has_aux=True)(params)
                p2, o2, _ = adamw.update(ocfg, g, opt_state, params)
                return p2, o2, l

            with jax.set_mesh(mesh):
                lowered = jax.jit(step, in_shardings=(p_sh, None, None)
                                  ).lower(params_abs, opt_abs, batch_abs)
                compiled = lowered.compile()
            txt = compiled.as_text()
            has_coll = any(k in txt for k in
                           ("all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute"))
            print("compiled", len(txt), "collectives:", has_coll)
            assert has_coll
        """)
        assert "compiled" in out


class TestElasticResharding:
    def test_checkpoint_restores_on_shrunk_mesh(self):
        """Save params sharded on a 2x4 mesh; restore onto 1x4 (simulating
        the loss of half the chips) — values identical, new shardings
        applied.  This is the elastic-rescale path end to end."""
        out = run_py("""
            import tempfile
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from jax.sharding import NamedSharding, PartitionSpec as PS
            from repro.checkpoint.manager import CheckpointManager
            from repro.configs import get_arch
            from repro.models.model_zoo import build_model
            from repro.parallel import sharding as shd
            from repro.parallel.fault_tolerance import plan_elastic_remesh

            cfg = get_arch("glm4-9b").reduced()
            model = build_model(cfg)
            mesh_a = make_mesh((2, 4), ("data", "model"))
            sh_a = shd.tree_shardings(model.params_spec(), mesh_a)
            params = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(p, s),
                model.init(jax.random.PRNGKey(0)), sh_a)

            with tempfile.TemporaryDirectory() as d:
                mgr = CheckpointManager(d, async_save=False)
                mgr.save(5, {"params": params})
                # lose 4 chips: plan keeps tp=4, data 2->1
                data, tp = plan_elastic_remesh(4, model_parallel=4)
                assert (data, tp) == (1, 4)
                mesh_b = make_mesh((1, 4), ("data", "model"))
                sh_b = shd.tree_shardings(model.params_spec(), mesh_b)
                got = mgr.restore({"params": params},
                                  shardings={"params": sh_b})["params"]
            a = jax.tree_util.tree_leaves(params)
            b = jax.tree_util.tree_leaves(got)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(
                    np.asarray(x, np.float32), np.asarray(y, np.float32))
            print("ok")
        """)
        assert "ok" in out


class TestDataParallelEquivalence:
    def test_sharded_loss_matches_single_device(self):
        """The same batch gives the same loss on a 2x4 mesh as unsharded —
        the sharding layer must be semantics-preserving."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.configs import get_arch
            from repro.models.model_zoo import build_model
            from repro.parallel import sharding as shd

            cfg = get_arch("glm4-9b").reduced().scaled(dtype="float32")
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            batch = model.make_batch(jax.random.PRNGKey(1), 8, 32, "train")
            base, _ = jax.jit(lambda p, b: model.loss(p, b))(params, batch)

            mesh = make_mesh((2, 4), ("data", "model"))
            p_sh = shd.tree_shardings(model.params_spec(), mesh)
            params_s = jax.tree_util.tree_map(jax.device_put, params, p_sh)
            with jax.set_mesh(mesh):
                sharded, _ = jax.jit(
                    lambda p, b: model.loss(p, b))(params_s, batch)
            a, b = float(base), float(sharded)
            assert abs(a - b) / abs(a) < 1e-4, (a, b)
            print("ok", a, b)
        """)
        assert "ok" in out

    def test_sharded_moe_loss_matches(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.configs import get_arch
            from repro.models.model_zoo import build_model
            from repro.parallel import sharding as shd

            cfg = get_arch("arctic-480b").reduced().scaled(
                dtype="float32", n_experts=8, capacity_factor=4.0)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            batch = model.make_batch(jax.random.PRNGKey(1), 8, 32, "train")
            base, _ = jax.jit(lambda p, b: model.loss(p, b))(params, batch)
            mesh = make_mesh((2, 4), ("data", "model"))
            p_sh = shd.tree_shardings(model.params_spec(), mesh)
            params_s = jax.tree_util.tree_map(jax.device_put, params, p_sh)
            with jax.set_mesh(mesh):
                sharded, _ = jax.jit(
                    lambda p, b: model.loss(p, b))(params_s, batch)
            a, b = float(base), float(sharded)
            # shard_map MoE groups tokens per data shard instead of per
            # sequence; with cf=4 nothing drops and losses agree tightly
            assert abs(a - b) / abs(a) < 5e-3, (a, b)
            print("ok", a, b)
        """)
        assert "ok" in out
