"""The round's stages are named in its compiled HLO.

``wssl_round`` runs its stages under ``jax.named_scope`` (``wssl.client``,
``wssl.server``, ``wssl.optimizer``, ``wssl.validate``,
``wssl.aggregate``) and attention its core under ``attn_core``.  The names
reach each optimized instruction's ``op_name``, which is what a profiler
trace's device time is attributed by.  On a tiny registered config, for the
flat round, the client-chunked scan and the bounded-staleness round (one
body with the flat round, at a finite deadline):

* every dot, convolution and fusion that carries a ``wssl.`` token carries
  one stage alone (no stage nests in another);
* each of the five stages has ops, and the client's backward (called
  outside the forward's trace) still carries ``wssl.client``;
* the attention core carries ``attn_core`` for the dense and the flash
  (``chunked``) paths, in the forward and in the backward.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.config import (AsyncRoundsConfig, TrainConfig, WSSLConfig,
                          get_arch, reduced)
from repro.core.async_round import init_async_state, make_async_round_fn
from repro.core.round import init_state, make_round_fn

STAGES = ("wssl.client", "wssl.server", "wssl.optimizer", "wssl.validate",
          "wssl.aggregate")
ROUNDS = ("flat", "chunk2", "async")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+)\s+=\s.*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@functools.lru_cache(maxsize=None)
def _ops(round_kind, impl):
    """[(instruction, opcode, op_name)] of the compiled round's dots,
    convolutions and fusions."""
    model = reduced(get_arch("musicgen-medium")).replace(num_layers=2)
    kw = ({"async_rounds": AsyncRoundsConfig(deadline=1.0)}
          if round_kind == "async" else {})
    w = WSSLConfig(num_clients=4, participation_fraction=0.5, split_layer=1,
                   **kw)
    t = TrainConfig(remat=True,
                    client_chunk=2 if round_kind == "chunk2" else None)
    state = init_state(jax.random.PRNGKey(0), model, w, t)[0]
    toks = jnp.zeros((4, 1, 16), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    val = {"tokens": toks[0], "labels": toks[0]}
    if round_kind == "async":
        rf = make_async_round_fn(model, w, t, impl=impl, donate=True)
        args = (state, init_async_state(state), batch, val, None, None, None,
                None)
    else:
        rf = make_round_fn(model, w, t, impl=impl, donate=True)
        args = (state, batch, val, None, None, None)
    text = rf._jitted.lower(*args).compile().as_text()
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and m.group(2) in ("dot", "convolution", "fusion"):
            p = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), p.group(1) if p else ""))
    assert out
    return out


@pytest.mark.parametrize("round_kind", list(ROUNDS))
def test_each_op_carries_one_stage(round_kind):
    for name, _, path in _ops(round_kind, "dense"):
        stages = set(re.findall(r"\bwssl\.\w+", path))
        assert len(stages) <= 1, (name, path)
        assert stages <= set(STAGES), (name, path)


@pytest.mark.parametrize("round_kind", list(ROUNDS))
def test_every_stage_has_ops(round_kind):
    paths = [p for _, _, p in _ops(round_kind, "dense")]
    for stage in STAGES:
        assert any(stage in p for p in paths), stage


@pytest.mark.parametrize("round_kind", list(ROUNDS))
def test_client_backward_carries_the_client_stage(round_kind):
    paths = [p for _, _, p in _ops(round_kind, "dense")]
    assert any("wssl.client" in p and "transpose(" in p for p in paths)
    assert any("wssl.client" in p and "transpose(" not in p for p in paths)


@pytest.mark.parametrize("impl", ["dense", "chunked"])
@pytest.mark.parametrize("round_kind", list(ROUNDS))
def test_attention_core_is_named(round_kind, impl):
    attn = [p for _, _, p in _ops(round_kind, impl)
            if "attn_core" in p]
    assert any("transpose(" in p for p in attn)
    assert any("transpose(" not in p for p in attn)
    # the core runs in the client, the server and validation alike
    for stage in ("wssl.client", "wssl.server", "wssl.validate"):
        assert any(stage in p for p in attn), stage
