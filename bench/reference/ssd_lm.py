"""Plain float32 Mamba-2 language model (arXiv:2405.21060), in the layout
and with the departures of the configuration file.

Each layer: x + Mamba2(RMSNorm(x)), with
  z, x', B, C, dt' = x Wz, x Wx, x WB, x WC, x Wdt
  x' = silu(causal_conv(x')),  [B, C] = silu(causal_conv([B, C]))
  dt = softplus(dt' + dt_bias),  A = -exp(A_log)
  y  = SSD(x' * dt, A * dt, B, C) + D * x'
  out = (RMSNorm(y) * silu(z)) Wo
and the SSD is the chunked algorithm of the paper's minimal listing
(``ssd_minimal_discrete``), in float32, at the paper's chunk of 256 (the
result does not depend on the chunk).  The norms are RMSNorm with weight
``1 + scale`` (the program's parameterisation).  Output head: the tied
embedding.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.plain import rms_norm, run_layers, token_nll_sum

REF_CHUNK = 256


def dims(m):
    di = m["d_model"] * m["ssm_expand"]
    return di, di // m["ssm_head_dim"]


def param_spec(m):
    d, L, V, n, k = (m["d_model"], m["num_layers"], m["vocab_size"],
                     m["ssm_state"], m["ssm_conv"])
    di, h = dims(m)

    def w(*shape, scale=None):
        return ("normal", (L,) + shape, scale or 1.0 / math.sqrt(shape[0]))

    layer = {
        "norm1": {"scale": ("const", (L, d), 0.0)},
        "mixer": {
            "wz": w(d, di), "wx": w(d, di), "wB": w(d, n), "wC": w(d, n),
            "wdt": w(d, h), "wo": w(di, d, scale=1.0 / math.sqrt(di)),
            "conv_x": w(k, di, scale=1.0 / math.sqrt(k)),
            "conv_BC": w(k, 2 * n, scale=1.0 / math.sqrt(k)),
            "A_log": ("log_linspace", (L, h), 1.0, 16.0),
            "D": ("const", (L, h), 1.0),
            "dt_bias": ("const", (L, h), 0.0),
            "norm_scale": ("const", (L, di), 1.0),
        },
    }
    return {"embed": {"tok": ("normal", (V, d), d ** -0.5)},
            "stack": [layer], "rem": [],
            "final_norm": {"scale": ("const", (d,), 0.0)}}


def _segsum(a):
    """(..., T) -> (..., T, T): sum of a[j+1..i] below the diagonal,
    -inf above it."""
    t = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd(x, a, b, c, pr, chunk=REF_CHUNK):
    """x: (B, S, H, P) already scaled by dt; a: (B, S, H) = A * dt;
    b, c: (B, S, N) shared by the heads.  Returns y: (B, S, H, P)."""
    bs, s, h, p = x.shape
    q = min(chunk, s)
    nc = s // q
    x = x.reshape(bs, nc, q, h, p)
    b = b.reshape(bs, nc, q, -1)
    c = c.reshape(bs, nc, q, -1)
    a = a.reshape(bs, nc, q, h).transpose(0, 3, 1, 2)        # (B, H, c, l)
    a_cum = jnp.cumsum(a, axis=-1)
    # 1. within each chunk
    el = jnp.exp(_segsum(a))                                  # (B,H,c,l,s)
    scores = pr.einsum("bcln,bcsn->bcls", c, b)
    y_diag = pr.einsum("bhcls,bcshp->bclhp", scores[:, None] * el, x)
    # 2. the state each chunk leaves
    decay = jnp.exp(a_cum[..., -1:] - a_cum)                  # (B,H,c,l)
    states = pr.einsum("bcln,bhcl,bclhp->bchpn", b, decay, x)
    # 3. states passed between chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0),
                                                           (1, 0)))))
    states = pr.einsum("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    # 4. the incoming state's part of each output
    y_off = pr.einsum("bcln,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cum))
    return (y_diag + y_off).reshape(bs, s, h, p)


def _causal_conv(x, w):
    """Depthwise causal convolution. x: (B, S, C), w: (k, C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(w[i] * xp[:, i:i + x.shape[1]] for i in range(k))


def layer(p, x, m, pr):
    eps = m["norm_eps"]
    di, h = dims(m)
    n = m["ssm_state"]
    mx = p["mixer"]
    u = rms_norm(x, 1.0 + p["norm1"]["scale"], eps)
    z = pr.einsum("bsd,de->bse", u, mx["wz"])
    xi = pr.einsum("bsd,de->bse", u, mx["wx"])
    bc = jnp.concatenate([pr.einsum("bsd,dn->bsn", u, mx["wB"]),
                          pr.einsum("bsd,dn->bsn", u, mx["wC"])], -1)
    dt = jax.nn.softplus(pr.einsum("bsd,dh->bsh", u, mx["wdt"])
                         + mx["dt_bias"])
    xi = jax.nn.silu(_causal_conv(xi, mx["conv_x"]))
    bc = jax.nn.silu(_causal_conv(bc, mx["conv_BC"]))
    bsz, s, _ = x.shape
    xh = xi.reshape(bsz, s, h, di // h)
    a = -jnp.exp(mx["A_log"])
    y = ssd(xh * dt[..., None], a * dt, bc[..., :n], bc[..., n:], pr)
    y = (y + mx["D"][:, None] * xh).reshape(bsz, s, di)
    y = rms_norm(y, mx["norm_scale"], 1e-6) * jax.nn.silu(z)
    return x + pr.einsum("bse,ed->bsd", y, mx["wo"])


def embed(client, tokens, m):
    return client["embed"]["tok"][tokens]


def head_nll_sum(server, h, labels, m, pr):
    h = rms_norm(h, 1.0 + server["final_norm"]["scale"], m["norm_eps"])
    tok = server["embed"]["tok"]
    return token_nll_sum(lambda hi: pr.einsum("bsd,vd->bsv", hi, tok),
                         h, labels)


def stack_forward(stack, x, m, pr):
    return run_layers(lambda p, h: layer(p, h, m, pr), stack, x)


def logits(p, h, m, pr):
    """Final norm and the tied head: (B, S, d) -> (B, S, V)."""
    h = rms_norm(h, 1.0 + p["final_norm"]["scale"], m["norm_eps"])
    return pr.einsum("bsd,vd->bsv", h, p["embed"]["tok"])
