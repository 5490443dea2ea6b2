"""chip_smoke.py rehearsed on the CPU at toy sizes.

The script itself refuses to run without a TPU; its phases are plain
functions, driven here with tiny shapes and interpret-mode kernels so a
wrong path, argument or check fails before any chip time is spent.
"""
import importlib.util
import os

import numpy as np
import pytest

from repro.configs import get_arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = dict(prompt_lens=(8, 20, 33, 64, 9, 17, 40, 100), new=6)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    out = capsys.readouterr().out
    assert '"ok"' not in out and "platform cpu" in out


def test_refuses_forced_interpret(smoke, capsys, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    assert smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_phase_at_toy_widths(smoke, capsys):
    smoke.kernel_phase(np.random.default_rng(0), smoke.CompileClock(),
                       interpret=True,
                       widths=dict(act=(16, 200), softmax=(16, 64),
                                   mac=(16, 64, 32), flash=(4, 2, 64, 16),
                                   wkv=(4, 32, 16)))
    out = capsys.readouterr().out
    assert out.count(": PASS") == 5


def test_serve_phases_on_reduced_glm4(smoke, capsys):
    model, params = smoke.build_glm4(0, cfg=get_arch("glm4-9b").reduced())
    clock = smoke.CompileClock()
    smoke.serve_phases(0, clock, model, params, **PROMPTS)
    out = capsys.readouterr().out
    assert out.count("reference check: PASS") == 2
    # both engines' programs were compiled under the clock
    assert clock.total > 0 and clock.summary().startswith("compile ")


def test_reference_check_rejects_a_wrong_token(smoke):
    model, params = smoke.build_glm4(0, cfg=get_arch("glm4-9b").reduced())
    reqs = smoke.make_requests(model.cfg, 0, prompt_lens=(8, 12), new=2)
    ref = smoke.Reference(model, params)
    # the reference's least likely next token at every step
    outs = {}
    for r in reqs:
        toks = np.asarray(r.prompt)[None]
        lg = np.asarray(ref.fwd(dict(params), toks), np.float32)[0, -1]
        outs[r.rid] = [int(lg.argmin())] * 2
    with pytest.raises(AssertionError, match="trails the reference max"):
        ref.check(reqs, outs, smoke.MARGIN_BF16, "wrong tokens")
