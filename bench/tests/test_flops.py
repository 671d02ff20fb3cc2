"""The model-FLOP functions against XLA's cost analysis of the program's
un-rematerialised forward, compiled for the CPU at a reduced size.  One
layer, because XLA counts the body of a scanned layer stack once; the
functions count matrix products only, so they read a few percent under
XLA's count, which adds the elementwise work."""

import importlib
import json

import pytest

from common import BENCH

CASES = [
    ("mamba2-370m", dict(num_layers=1, d_model=512, vocab_size=1024,
                         ssm_chunk=64), 512),
    ("mamba2-370m", dict(num_layers=1, d_model=1024, vocab_size=4096,
                         ssm_chunk=128), 1024),
    ("musicgen-medium-12l", dict(num_layers=1, d_model=512, num_heads=8,
                                 num_kv_heads=8, head_dim=64, d_ff=2048,
                                 vocab_size=1024), 512),
]


@pytest.mark.parametrize("config,shrink,seq", CASES)
def test_forward_flops_match_xla(config, shrink, seq):
    import jax
    import jax.numpy as jnp
    from drivers.train_rounds import wssl_configs
    from repro.models import transformer as tf

    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg["model"].update(shrink)
    traffic = json.loads((BENCH / "traffic" / "train.seq2k.json").read_text())
    model, _, _ = wssl_configs(dict(cfg, split_layer=0), traffic)
    params, _ = tf.init_params(jax.random.PRNGKey(0), model)
    toks = jnp.zeros((2, seq), jnp.int32)
    compiled = jax.jit(lambda p, t: tf.forward(
        p, model, t, impl="dense", remat=False)[0]).lower(
            params, toks).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    flops = importlib.import_module(f"flops.{cfg['family']}")
    mine = flops.forward_flops(cfg["model"], 2 * seq, seq)
    assert 0.90 * cost["flops"] <= mine <= cost["flops"]


def test_train_flops_count_the_selected_rows_three_times():
    from flops import ssd_lm

    cfg = json.loads((BENCH / "configs" / "mamba2-370m.json").read_text())
    tr = json.loads((BENCH / "traffic" / "train.seq2k.json").read_text())
    k = 2
    assert ssd_lm.train_flops(cfg["model"], tr) == 3 * ssd_lm.forward_flops(
        cfg["model"], k * tr["rows_per_client"] * tr["seq"], tr["seq"])
