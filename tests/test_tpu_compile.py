"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler ships with jax, so lowering a kernel or a jitted step
for a *described* ``v5e:2x2`` topology raises what the chip's compiler
would raise: a block that breaks the (8, 128) tiling, a slice Mosaic
cannot lower, more VMEM than a kernel may use, a program that does not
fit the chip's 16 GB.  Interpret-mode tests see none of that.

Nothing runs, so these tests say nothing about results or speed.  The
topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, and pytest-xdist
workers each import every test file.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels as K
from repro.configs import get_arch
from repro.kernels import common
from repro.models.model_zoo import build_model

# one v5e chip's HBM
V5E_HBM_BYTES = 16 * 1024 ** 3
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without the chip: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branches(monkeypatch):
    """Steer the code's own TPU branches (tile caps) as on the chip:
    here ``jax.devices()`` is the CPU.  Tiles picked off-TPU are cached
    per shape, so the cache starts and ends empty."""
    monkeypatch.setattr(common, "on_tpu", lambda: True)
    common.clear_block_cache()
    yield
    common.clear_block_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; the Pallas kernel must be in it."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (family, argument shapes, forward at one main-path width)
_FORWARD = {
    "cordic_act": (((256, 13696), jnp.float32),
                   lambda x: K.cordic_act(x, "tanh", interpret=False)),
    "cordic_softmax": (((256, 4096), jnp.float32),
                       lambda x: K.cordic_softmax(x, interpret=False)),
    "cordic_mac": (((256, 4096), jnp.float32), ((4096, 512), jnp.float32),
                   lambda x, w: K.cordic_matmul(x, w, interpret=False)),
    # glm4-9b: 32 query heads over 2 kv heads, dh 128, one 2048 prompt
    "flash_attention": (((1, 2048, 32, 128), jnp.bfloat16),
                        ((1, 2048, 2, 128), jnp.bfloat16),
                        ((1, 2048, 2, 128), jnp.bfloat16),
                        lambda q, k, v: K.flash_attention(q, k, v,
                                                          interpret=False)),
    # rwkv6-3b: 40 heads of 64
    "wkv": (((1, 1024, 40, 64), jnp.bfloat16),) * 4
    + (((40, 64), jnp.bfloat16),
       lambda r, k, v, w, u: K.wkv(r, k, v, w, u, interpret=False)),
    # the serving variant over an int8 state with per-row scales
    "wkv.q8": (((1, 1024, 40, 64), jnp.bfloat16),) * 4
    + (((40, 64), jnp.bfloat16), ((1, 40, 64, 64), jnp.int8),
       ((1, 40, 64), jnp.float32),
       lambda r, k, v, w, u, s, ss: K.wkv_q8(r, k, v, w, u, s, ss,
                                             interpret=False)),
}


@pytest.mark.parametrize("family", sorted(_FORWARD))
def test_forward_compiles(family, one_chip):
    *shapes, fn = _FORWARD[family]
    _compile(fn, *(_sds(s, d, one_chip) for s, d in shapes))


@pytest.mark.parametrize("family", ["flash_attention", "wkv"])
def test_backward_compiles(family, one_chip, tpu_branches):
    *shapes, fn = _FORWARD[family]
    n = len(shapes)

    def loss(*args):
        return fn(*args).astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=tuple(range(n))),
             *(_sds(s, d, one_chip) for s, d in shapes))


@pytest.fixture(scope="module")
def smoke_kernel_cases():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return {c[0]: c for c in mod.kernel_cases(np.random.default_rng(0))}


@pytest.mark.parametrize("family", ["cordic_act", "cordic_mac",
                                    "cordic_softmax", "flash_attention",
                                    "wkv"])
def test_chip_smoke_kernel_compiles(family, one_chip, smoke_kernel_cases):
    """The raw entry point, arguments and blocks chip_smoke.py runs."""
    _, kkw, _, args, *_ = smoke_kernel_cases[family]
    kernel = common.get_kernel(family).kernel
    _compile(lambda *a: kernel(*a, interpret=False, **kkw),
             *(_sds(a.shape, a.dtype, one_chip) for a in args))


def test_glm4_decode_step_fits_one_chip(one_chip):
    """One decode step of glm4-9b at published widths (2 of its 40
    layers) over 8 slots of 2048 tokens fits one chip's HBM."""
    cfg = get_arch("glm4-9b").scaled(n_layers=2)
    model = build_model(cfg)

    def place(t):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), t)

    params = place(model.abstract_params())
    state = place(model.init_slot_state(8, 2048, abstract=True))
    tokens = _sds((8, 1), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, s, t: model.decode_step(p, s, {"tokens": t}),
        donate_argnums=(1,)).lower(params, state, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 2 * 204e6 * 2   # the weights are in
    assert total < V5E_HBM_BYTES, total


_BOOKKEEPING = {"parameter", "get-tuple-element", "tuple", "bitcast",
                "constant"}


def _module_ops(hlo: str):
    """Device ops (bookkeeping left out) of each computation of a compiled
    module's text: {computation: [(op, [output shapes])]}."""
    ops, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            ops[name] = []
        elif name and line.startswith("  ") and " = " in line:
            rhs = line.split(" = ", 1)[1]
            op = re.search(r"\s([a-z][a-z0-9\-]*)\((?:%|\))", rhs)
            if op and op.group(1) not in _BOOKKEEPING:
                shapes = [tuple(int(d) for d in dims.split(",") if d)
                          for dims in re.findall(r"[a-z0-9]+\[([\d,]*)\]",
                                                 rhs[:op.start()])]
                ops[name].append((op.group(1), shapes))
    return ops


def _loop_bodies(hlo: str):
    """The device ops of each while loop's body, as :func:`_module_ops`."""
    ops = _module_ops(hlo)
    return [ops[b] for b in re.findall(r" while\(.*?body=%([\w.\-]+)", hlo)]


def _loop_body_sizes(hlo: str):
    """Device ops (bookkeeping left out) in each while loop's body of a
    compiled module's text."""
    return [len(body) for body in _loop_bodies(hlo)]


def _stacked_state_copies(hlo: str, state):
    """Copies the module makes of a whole stacked state leaf."""
    stacked = {leaf.shape for leaf in jax.tree.leaves(state)
               if leaf.ndim > 2}
    return [(op, shapes) for ops in _module_ops(hlo).values()
            for op, shapes in ops if op == "copy" and set(shapes) & stacked]


def test_rwkv6_prefill_runs_no_loop_a_token(one_chip):
    """A one-row rwkv6-3b prefill (published widths, 2 of its 32 layers,
    a 256-token bucket) compiles its wkv scan with no device loop a
    token: each loop left (layers, 64-token chunks) holds at least a
    chunk's worth of ops.  A loop a token costs 8 device ops a token and
    layer (its slices, step, update, counter and test), and a profiler
    trace of a serving window holds every one of them."""
    cfg = get_arch("rwkv6-3b").scaled(n_layers=2)
    model = build_model(cfg)
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          model.abstract_params())
    tokens = _sds((1, 256), jnp.int32, one_chip)
    lengths = _sds((1,), jnp.int32, one_chip)
    hlo = jax.jit(
        lambda p, t, n: model.prefill(p, {"tokens": t}, headroom=0,
                                      lengths=n)
    ).lower(params, tokens, lengths).compile().as_text()
    sizes = _loop_body_sizes(hlo)
    assert sizes and min(sizes) >= 64, sizes


def test_rwkv6_decode_step_updates_state_in_place(one_chip):
    """rwkv6-3b's decode step (published widths, 2 of its 32 layers, 16
    rows) carries its stacked token-shift boundaries and wkv state
    through the layer loop: no second stacked copy of the state is made
    (temps under one wkv stack, 21 MB) and none is copied back after the
    loop."""
    cfg = get_arch("rwkv6-3b").scaled(n_layers=2)
    model = build_model(cfg)

    def place(t):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), t)

    params = place(model.abstract_params())
    state = place(model.init_slot_state(16, 2048, abstract=True))
    tokens = _sds((16, 1), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, s, t: model.decode_step(p, s, {"tokens": t}),
        donate_argnums=(1,)).lower(params, state, tokens).compile()
    wkv_bytes = state.wkv.size * state.wkv.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < wkv_bytes, (temp, wkv_bytes)
    assert not _stacked_state_copies(compiled.as_text(), state)


def test_falcon_h1_serves_at_published_widths_on_one_chip(one_chip):
    """falcon-h1-34b at published widths (6 of its 72 layers, a pipeline
    stage and the embedding and head) over 16 slots of 8704 positions:
    the decode step fits one chip's HBM with its KV cache and float32
    Mamba-2 state, and a one-row prefill (a 512 bucket, where attention
    has no loop) compiles to two device loops, the layers and the SSD
    scan's 128-token chunks, and none a token."""
    cfg = get_arch("falcon-h1-34b").scaled(n_layers=6)
    model = build_model(cfg)

    def place(t):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), t)

    params = place(model.abstract_params())
    state = place(model.init_slot_state(16, 8704, abstract=True))
    tokens = _sds((16, 1), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, s, t: model.decode_step(p, s, {"tokens": t}),
        donate_argnums=(1,)).lower(params, state, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 12e9    # weights, cache and state
    assert total < V5E_HBM_BYTES, total
    # the layer loop carries the stacked cache and state and writes each
    # layer's slice in place: no second stacked copy (2.1 GB) is made
    # inside or after the loop, and the loop reads a layer's K/V in place
    # (a whole layer's cache, 143 MB, in any layout, is never an output)
    assert mem.temp_size_in_bytes < 1e9, mem.temp_size_in_bytes
    hlo = compiled.as_text()
    assert not _stacked_state_copies(hlo, state)
    layer_cache = sorted(state.cache_k.shape[1:])
    (body,) = _loop_bodies(hlo)
    whole = [(op, shapes) for op, shapes in body
             if any(sorted(d for d in sh if d > 1) == layer_cache
                    for sh in shapes)]
    assert not whole, whole

    tokens = _sds((1, 512), jnp.int32, one_chip)
    lengths = _sds((1,), jnp.int32, one_chip)
    hlo = jax.jit(
        lambda p, t, n: model.prefill(p, {"tokens": t}, headroom=0,
                                      lengths=n)
    ).lower(params, tokens, lengths).compile().as_text()
    sizes = _loop_body_sizes(hlo)
    assert len(sizes) == 2, sizes
