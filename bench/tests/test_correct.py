"""The comparison that decides ``correct``, driven at a tiny size on the
CPU through the training driver (the look for a chip skipped): a sound
program passes each cell's limits, and each of these comes out not
correct -- the control (the reference in the program's place, every
product's operands rounded to float8 e4m3), and the program with a fault
planted in its round: the state returned unchanged, each client's loss
taken over half of its rows (half of its positions where it has one row)
with every row forwarded.  The first k clients selected in place of the
Gumbel draw reads a selection gap.  The program runs at float32 compute
here, so that only the faults differ from the reference."""

import json
import math

import pytest

import tiny
from common import BENCH
from calibrate import CONTROL
from drivers.train_rounds import TrainCell, compare

CELLS = [w["name"] for w in json.loads((BENCH.parent / "BENCHMARK.json")
                                       .read_text())["workloads"]]


def _cell(workload):
    ctx = tiny.cell(workload)
    ctx.config["model"]["dtype"] = "float32"
    return ctx, TrainCell(ctx)


def _unchanged(cell, monkeypatch):
    import jax
    import jax.numpy as jnp

    round_fn = cell.round_fn

    def broken(state, batch, val):
        _, m = round_fn(jax.tree.map(jnp.copy, state), batch, val)
        return state, m
    cell.round_fn = broken


def _half_rows(cell, monkeypatch):
    """Every row forwarded, each client's loss over half of its rows (or
    of its positions where it has one row)."""
    import repro.core.round as rnd

    losses = rnd._per_client_losses

    def half(cfg, server, acts, labels, *args, **kw):
        b, s = labels.shape[1:]
        cut = ((slice(None), slice(0, b // 2)) if b > 1
               else (slice(None), slice(None), slice(0, s // 2)))
        return losses(cfg, server, acts[cut], labels[cut], *args, **kw)
    monkeypatch.setattr(rnd, "_per_client_losses", half)


def _first_k(cell, monkeypatch):
    """The first k clients selected in every round after the first."""
    import jax.numpy as jnp
    import repro.core.wssl as wssl

    def first(rng, weights, cfg, round_index, idx=None, penalty=None):
        n = cfg.num_clients
        mask = (jnp.arange(n) < cfg.num_selected()).astype(jnp.float32)
        return jnp.where(round_index == 0, jnp.ones_like(mask), mask)
    monkeypatch.setattr(wssl, "participation_mask", first)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_program_passes_and_control_fails(workload):
    ctx, cell = _cell(workload)
    out = cell.run()
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 3
    state, prog, key, _ = cell.checked_rounds(ctx.seed)
    del state
    ref = cell.reference(key, prog["masks"])
    control = cell.reference(key, prog["masks"], round_to=CONTROL)
    checks = compare(control, ref, ctx.limits,
                     ctx.traffic["moved_floor"])
    assert not all(c["ok"] for c in checks), checks


@pytest.mark.parametrize("fault", [_unchanged, _half_rows],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_round_is_not_correct(workload, fault, monkeypatch):
    _, cell = _cell(workload)
    fault(cell, monkeypatch)
    out = cell.run()
    bad = [c for c in out["checks"] if not c["ok"]]
    assert bad, out["checks"]
    values = {c["name"]: c["value"] for c in out["checks"]}
    if fault is _unchanged:
        assert values["change_norm_gap"] == pytest.approx(1.0, abs=1e-3)
    if fault is _half_rows:
        # the cut still carries every row: the bytes cannot tell
        assert values["cut_bytes_gap"] == 0.0
    assert all(math.isfinite(c["value"]) or not c["ok"]
               for c in out["checks"])


@pytest.mark.parametrize("workload", CELLS)
def test_wrong_selection_reads_a_selection_gap(workload, monkeypatch):
    """``select_gap`` is not held yet (its fault has no chip readings):
    it reads 0 for the program's own draw and more for the first k."""
    gaps = []
    for fault in (None, _first_k):
        ctx, cell = _cell(workload)
        if fault:
            fault(cell, monkeypatch)
        out = cell.run()
        floor = ctx.traffic["moved_floor"]
        values = {c["name"]: c["value"] for c in compare(
            out["program"], out["reference"], {}, floor)}
        gaps.append(values["select_gap"])
    assert gaps[0] == 0.0 and gaps[1] > 0.0, gaps
