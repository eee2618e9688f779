"""The phases of ``chip_smoke.py`` end to end on the CPU at smoke size.

The script's ``main`` pins the published widths and refuses to run
without a TPU; its phases take the configs, so this test drives them
with smoke configs. The hpc tier runs with ``use_kernels=True``; the
test steers every Pallas call into interpret mode (the program itself
has no such option), so the same kernels the chip compiles run here.
"""

import importlib.util
import os

import pytest

from repro.configs import get_smoke_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret_kernels(monkeypatch):
    from jax.experimental import pallas as pl

    call = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **k: call(*a, **{**k, "interpret": True}))


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phases_at_smoke_size(smoke, interpret_kernels):
    hpc = get_smoke_config("minitron-8b").replace(
        vocab_size=384, param_dtype="bfloat16", use_kernels=True)
    local = get_smoke_config("xlstm-125m").replace(vocab_size=384)
    plan = smoke.sizing(hpc, local, max_seq=128, prefix_pages=16)
    assert plan["kv_bytes_per_token_per_layer"] == 2 * 2 * 2 * 16
    # two live copies x 17 pages x (L=2, K and V, Hkv=2, page 16, D=16, bf16)
    assert plan["hpc"]["pool"] == 2 * 17 * 2 * 2 * 2 * 16 * 16 * 2
    assert plan["local"]["pool"] > 0 and plan["local"]["slots"] > 0
    system = smoke.build(hpc, local, max_seq=128, prefix_pages=16, slots=2)
    try:
        assert system.engines["hpc"].cfg.d_model == hpc.d_model
        assert smoke.warm(system, max_tokens=4) > 0
        readings = smoke.serve(system, max_tokens=6)
        assert {r["alias"] for r in readings} == set(smoke.PROMPTS)
        assert all(r["completion_tokens"] >= 1 for r in readings)
        errs = smoke.compare(system, n_forced=4)
        smoke.check_bounds(errs)
        assert errs["kernel_vs_ref"] > 0 or errs["int8_vs_bf16"] > 0
    finally:
        for eng in system.engines.values():
            eng.shutdown()
        system.gateway.shutdown()
        system.endpoint.shutdown()
