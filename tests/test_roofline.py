"""Roofline machinery: the HLO structural cost parser must apply while-loop
trip counts (the thing XLA's own cost analysis gets wrong) and the
three-term report must classify bottlenecks sanely."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import analysis as ra
from repro.roofline.hlo_cost import analyze_text


def _scan_fn(w, x):
    def body(x, wi):
        return jnp.tanh(x @ wi), None
    y, _ = jax.lax.scan(body, x, w)
    return y.sum()


def _unrolled_fn(w, x):
    for i in range(8):
        x = jnp.tanh(x @ w[i])
    return x.sum()


@pytest.fixture(scope="module")
def compiled_pair():
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    cs = jax.jit(_scan_fn).lower(w, x).compile()
    cu = jax.jit(_unrolled_fn).lower(w, x).compile()
    return cs, cu


def test_parser_applies_trip_counts(compiled_pair):
    cs, cu = compiled_pair
    ts = analyze_text(cs.as_text())
    tu = analyze_text(cu.as_text())
    expected = 8 * 2 * 16 * 128 * 128
    assert ts["flops"] == pytest.approx(expected, rel=0.05)
    assert tu["flops"] == pytest.approx(expected, rel=0.05)
    # XLA's own analysis undercounts the scan by ~8x — the bug we fix
    xla = cs.cost_analysis()
    assert xla["flops"] < 0.3 * ts["flops"]


def test_parser_counts_backward(compiled_pair):
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    cg = jax.jit(jax.grad(_scan_fn)).lower(w, x).compile()
    tg = analyze_text(cg.as_text())
    fwd = 8 * 2 * 16 * 128 * 128
    assert 2.2 * fwd < tg["flops"] < 4.0 * fwd   # fwd + 2x bwd


def test_bytes_same_order_as_xla_on_unrolled(compiled_pair):
    """On tiny single-device programs fusion boundaries differ, so we only
    require same-order agreement here; on the representative reduced-gemma
    4-layer unrolled train step the parser matched XLA's bytes-accessed
    exactly (3.264e9 both — recorded in EXPERIMENTS.md §Dry-run notes)."""
    _, cu = compiled_pair
    tu = analyze_text(cu.as_text())
    xla = cu.cost_analysis()
    assert 0.5 * xla["bytes accessed"] < tu["bytes"] < 5 * xla["bytes accessed"]


def test_roofline_report_bottleneck():
    r = ra.RooflineReport(arch="x", shape="train_4k", mesh="16x16",
                          flops_per_device=197e12,      # 1 s compute
                          bytes_per_device=819e9 / 10,  # 0.1 s memory
                          coll_bytes_per_device=50e9 / 100,
                          model_flops_global=197e12 * 256 * 0.5,
                          chips=256)
    assert r.bottleneck == "compute"
    assert r.t_compute == pytest.approx(1.0)
    assert r.mfu_bound == pytest.approx(0.5)
    assert r.model_flops_ratio == pytest.approx(0.5)


def test_model_flops_kinds():
    from repro.config import INPUT_SHAPES, get_arch
    cfg = get_arch("gemma-2b")
    tr = ra.model_flops(cfg, INPUT_SHAPES["train_4k"])
    pf = ra.model_flops(cfg, INPUT_SHAPES["prefill_32k"])
    dc = ra.model_flops(cfg, INPUT_SHAPES["decode_32k"])
    assert tr == pytest.approx(6 * cfg.param_count() * 256 * 4096, rel=0.01)
    assert pf == pytest.approx(2 * cfg.param_count() * 32 * 32768, rel=0.01)
    assert dc == pytest.approx(2 * cfg.param_count() * 128, rel=0.01)
    moe = get_arch("olmoe-1b-7b")
    assert ra.model_flops(moe, INPUT_SHAPES["train_4k"]) < \
        6 * moe.param_count() * 256 * 4096 * 0.25


def test_collective_regex_on_synthetic_lines():
    text = """
ENTRY %main (p: f32[8]) -> f32[8] {
  %ag = f32[64,512]{0,1} all-gather(%copy), channel_id=1
  %ar = f32[1024]{0} all-reduce(%x), channel_id=2
  ROOT %cp = f32[8]{0} copy(%ar)
}
"""
    out = analyze_text(text)
    assert out["coll_all-gather"] == 64 * 512 * 4
    assert out["coll_all-reduce"] == 1024 * 4
    assert out["coll_weighted"] == 64 * 512 * 4 + 2 * 1024 * 4
