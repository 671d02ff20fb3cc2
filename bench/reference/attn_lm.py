"""Plain float32 decoder-only transformer with LayerNorm, multi-head causal
attention and a GELU MLP: MusicGen's decoder (arXiv:2306.05284) with the
departures of the configuration file.

Each layer: x + Attn(LN1(x)), then x + W_down gelu(W_up LN2(x)); scores
over all earlier positions in float32, scaled by 1/sqrt(head_dim); no
biases in the projections, no positional term.  Output head: LN_f, then
its own (d, V) matrix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.plain import layer_norm, run_layers, token_nll_sum


def param_spec(m):
    d, L, V = m["d_model"], m["num_layers"], m["vocab_size"]
    h, hd, f = m["num_heads"], m["head_dim"], m["d_ff"]

    def ln(lead=(L,)):
        return {"bias": ("const", lead + (d,), 0.0),
                "scale": ("const", lead + (d,), 1.0)}

    def w(*shape, scale):
        return ("normal", (L,) + shape, scale)

    layer = {
        "norm1": ln(),
        "mixer": {"wq": w(d, h, hd, scale=d ** -0.5),
                  "wk": w(d, h, hd, scale=d ** -0.5),
                  "wv": w(d, h, hd, scale=d ** -0.5),
                  "wo": w(h, hd, d, scale=1.0 / math.sqrt(h * hd))},
        "norm2": ln(),
        "mlp": {"wu": w(d, f, scale=d ** -0.5),
                "wd": w(f, d, scale=1.0 / math.sqrt(f))},
    }
    return {"embed": {"tok": ("normal", (V, d), d ** -0.5)},
            "stack": [layer], "rem": [], "final_norm": ln(()),
            "head": ("normal", (d, V), d ** -0.5)}


def layer(p, x, m, pr):
    eps = m["norm_eps"]
    a = p["mixer"]
    u = layer_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)
    q = pr.einsum("bsd,dhe->bshe", u, a["wq"])
    k = pr.einsum("bsd,dhe->bshe", u, a["wk"])
    v = pr.einsum("bsd,dhe->bshe", u, a["wv"])
    s = x.shape[1]
    scores = pr.einsum("bqhe,bkhe->bhqk", q, k) / math.sqrt(m["head_dim"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = pr.einsum("bhqk,bkhe->bqhe", probs, v)
    x = x + pr.einsum("bshe,hed->bsd", o, a["wo"])
    u = layer_norm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
    hid = jax.nn.gelu(pr.einsum("bsd,df->bsf", u, p["mlp"]["wu"]),
                      approximate=True)
    return x + pr.einsum("bsf,fd->bsd", hid, p["mlp"]["wd"])


def embed(client, tokens, m):
    return client["embed"]["tok"][tokens]


def head_nll_sum(server, h, labels, m, pr):
    fn = server["final_norm"]
    h = layer_norm(h, fn["scale"], fn["bias"], m["norm_eps"])
    head = server["head"]
    return token_nll_sum(lambda hi: pr.einsum("bsd,dv->bsv", hi, head),
                         h, labels)


def stack_forward(stack, x, m, pr):
    return run_layers(lambda p, h: layer(p, h, m, pr), stack, x)


def logits(p, h, m, pr):
    """Final norm and the head: (B, S, d) -> (B, S, V)."""
    fn = p["final_norm"]
    h = layer_norm(h, fn["scale"], fn["bias"], m["norm_eps"])
    return pr.einsum("bsd,dv->bsv", h, p["head"])
