"""Plain float32 building blocks of the reference models.

Every matrix product goes through :class:`Precision`: float32 operands at
``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in bfloat16), or,
for the control, operands rounded to a lower type first.  Parameters are
kept in the layout the benchmark makes them in (see ``weights.py``), which
is the program's own layout, so that one set of weights serves both.
Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Precision:
    """``round_to=None``: float32 throughout.  ``round_to=<dtype>``: each
    product's operands are rounded to that type, and accumulated in
    float32 (the control)."""

    def __init__(self, round_to=None):
        self.round_to = None if round_to is None else jnp.dtype(round_to)

    def r(self, a):
        a = a.astype(jnp.float32)
        if self.round_to is None:
            return a
        return a.astype(self.round_to).astype(jnp.float32)

    def einsum(self, spec, *ops):
        return jnp.einsum(spec, *(self.r(o) for o in ops), precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def rms_norm(x, weight, eps):
    """x / rms(x) * weight, over the last axis."""
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * weight


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def run_layers(layer_fn, stack, x):
    """Apply the layers of ``stack`` (leaves with a leading layer axis) in
    order, each layer recomputed in the backward pass to bound memory."""
    step = jax.checkpoint(lambda h, p: (layer_fn(p, h), None))
    x, _ = jax.lax.scan(step, x, stack)
    return x


def token_nll_sum(logits_fn, h, labels, chunk=256):
    """Sum over positions of -log p(label), with the (B, S, V) logits made
    one chunk of positions at a time."""
    b, s, d = h.shape
    c = min(chunk, s)
    while s % c:
        c -= 1
    hc = h.reshape(b, s // c, c, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, s // c, c).transpose(1, 0, 2)

    @jax.checkpoint
    def body(tot, xs):
        hi, yi = xs
        logits = logits_fn(hi)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yi[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, yc))
    return tot
