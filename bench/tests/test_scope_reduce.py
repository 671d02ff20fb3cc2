"""Device time per named stage: hand-made events and op paths with known
answers, the HLO text parser on a tiny round compiled on the CPU, the
dispatch offsets of the trace recorded on one v5e, and the stage cell
driven at a tiny size."""

from pathlib import Path

import pytest

from scope_reduce import (by_scope, dispatch_offsets, hlo_op_paths,
                          offset_summary, stage_of, top_ops)
from trace_reduce import read_events, reduce_events

TRACE = Path(__file__).parent / "data" / "v5e_matmul_loop.xplane.pb"
MS = 1_000_000
PATHS = {
    "fusion.1": "jit(f)/transpose(jvp(wssl.client))/dot_general",
    "fusion.2": "jit(f)/wssl.server/jvp(attn_core)/exp",
    "while.3": "jit(f)/wssl.validate/vmap()/while",
    "fusion.4": "jit(f)/wssl.validate/vmap()/while/body/closed_call/add",
    "fusion.5": "jit(f)/jit(_gumbel)/add",
    "copy.6": "",
    "fusion.7": "jit(f)/wssl.optimizer/wssl.client/mul",
}


def test_stage_is_the_outermost_wssl_token():
    assert stage_of("%fusion.1 = bf16[2] fusion(...)", PATHS) == \
        "wssl.client"
    assert stage_of("fusion.7", PATHS) == "wssl.optimizer"
    assert stage_of("fusion.5", PATHS) == "other"
    assert stage_of("copy.6", PATHS) == "other"
    assert stage_of("fusion.99", PATHS) == "unmapped"


def test_by_scope_self_times_and_cross_cut():
    devices = {
        "/device:TPU:0": [
            ("%fusion.1 = bf16[] a", 0, 2 * MS),            # client bwd
            ("%fusion.2 = bf16[] b", 2 * MS, 5 * MS),       # server, attn
            ("%while.3 = (f32[]) w", 5 * MS, 10 * MS),      # validate loop
            ("%fusion.4 = f32[] c", 6 * MS, 9 * MS),        # its body
            ("%fusion.5 = f32[] d", 10 * MS, 11 * MS),      # no stage
            ("%fusion.99 = f32[] e", 11 * MS, 12 * MS),     # not in module
            ("%fusion.7 = f32[] g", 12 * MS, 14 * MS),      # optimizer
            ("%fusion.1 = bf16[] a", 20 * MS, 22 * MS)],    # after window
        "/device:TPU:1": [("%fusion.2 = bf16[] b", 0, 2 * MS)],
    }
    spans = [("bench.window", 0, 15 * MS)]
    out = by_scope(devices, spans, PATHS)
    # seconds averaged over the two devices, like busy_s
    assert out == {
        "wssl.client": pytest.approx(2 / 2 / 1e3),
        "wssl.server": pytest.approx((3 + 2) / 2 / 1e3),
        "wssl.validate": pytest.approx((2 + 3) / 2 / 1e3),
        "other": pytest.approx(1 / 2 / 1e3),
        "unmapped": pytest.approx(1 / 2 / 1e3),
        "wssl.optimizer": pytest.approx(2 / 2 / 1e3),
        "attn_core": pytest.approx((3 + 2) / 2 / 1e3),
    }
    # the stages, other and unmapped make up busy where no ops overlap
    # but by nesting
    busy = reduce_events(devices, spans)["busy_s"]
    assert sum(v for k, v in out.items() if k != "attn_core") == \
        pytest.approx(busy)
    assert top_ops(devices, spans, PATHS, "wssl.validate") == \
        [["fusion.4", pytest.approx(3 / 2 / 1e3)],
         ["while.3", pytest.approx(2 / 2 / 1e3)]]
    assert top_ops(devices, spans, PATHS, "other", top=1) == \
        [["fusion.5", pytest.approx(1 / 2 / 1e3)]]


def test_by_scope_without_device_ops_reads_nothing():
    assert by_scope({"/device:TPU:0": []}, [], PATHS) == {}


def test_hlo_op_paths_of_a_tiny_round():
    """Every fusion of a tiny round's optimized HLO is found with its
    op_name, which names the round's stages."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.config import TrainConfig, WSSLConfig, get_arch, reduced
    from repro.core.round import init_state, make_round_fn

    model = reduced(get_arch("musicgen-medium")).replace(num_layers=2)
    w = WSSLConfig(num_clients=4, participation_fraction=0.5,
                   split_layer=1)
    t = TrainConfig(remat=True)
    state = init_state(jax.random.PRNGKey(0), model, w, t)[0]
    toks = jnp.zeros((4, 1, 16), jnp.int32)
    val = {"tokens": toks[0], "labels": toks[0]}
    text = make_round_fn(model, w, t, impl="dense", donate=True)._jitted \
        .lower(state, {"tokens": toks, "labels": toks}, val, None, None,
               None).compile().as_text()
    paths = hlo_op_paths(text)
    fusions = re.findall(r"^\s*(?:ROOT )?%(\S+) = .* fusion\(.*$", text,
                         re.M)
    assert len(fusions) > 10
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%(\S+) = .* fusion\(", line)
        if m:
            want = re.search(r'op_name="([^"]*)"', line)
            assert paths[m.group(1)] == (want.group(1) if want else "")
    stages = {stage_of(n, paths) for n in paths}
    assert {"wssl.client", "wssl.server", "wssl.optimizer",
            "wssl.validate", "wssl.aggregate"} <= stages
    assert any("attn_core" in p for p in paths.values())


def test_dispatch_offsets_of_the_recorded_v5e_trace():
    """Each call's first device op sits about a millisecond before the
    host's dispatch span: the device's clock runs before the host's."""
    devices, spans = read_events(TRACE)
    offsets = dispatch_offsets(devices, spans)
    assert len(offsets) == 5
    assert all(-1.2e-3 < o < -0.9e-3 for o in offsets)
    summary = offset_summary(offsets)
    assert summary["calls"] == 5
    assert summary["min_ms"] == pytest.approx(1e3 * min(offsets))


def test_dispatch_offsets_bracket_each_call():
    # host: dispatch, block, then 10 ms of input before the next dispatch;
    # the device's clock 2 ms after the host's
    spans = [("bench.dispatch", 0, 1 * MS), ("bench.block", 1 * MS, 20 * MS),
             ("bench.input", 20 * MS, 30 * MS),
             ("bench.dispatch", 30 * MS, 31 * MS),
             ("bench.block", 31 * MS, 50 * MS)]
    ops = [("a", 2 * MS, 10 * MS), ("b", 10 * MS, 21 * MS),   # call 1
           ("a", 32 * MS, 45 * MS), ("b", 45 * MS, 51 * MS)]  # call 2
    assert dispatch_offsets({"/device:TPU:0": ops}, spans) == \
        [pytest.approx(0.002), pytest.approx(0.002)]
    assert offset_summary([]) == {}


def test_stage_cell_reads_the_round_hlo_from_the_cache():
    """The stage cell at a tiny size on the CPU: the HLO text is taken
    after the first round with no compile, and the run's output carries
    the stage line (no device plane on the CPU: no stage times)."""
    import tiny
    from stage_times import StageCell, StageContext

    base = tiny.cell("musicgen-medium-12l.train.clip30s")
    ctx = StageContext(base.jax, base.devices, base.workload, base.config,
                       base.traffic, base.limits, base.seed, 0.2, 1)
    cell = StageCell(ctx)
    out = cell.run()
    assert ctx.hlo_compiles["backend_compiles"] == 0
    line = out["stages"]
    assert line["hlo_ops"] > 100
    assert line["rounds"] == out["readings"]["rounds"] > 0
    assert line["round_s"] == out["e2e"]["round_s"]

