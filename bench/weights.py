"""Weights made by the benchmark, on the device, from the seed.

A family's ``param_spec`` describes every leaf in the program's parameter
layout: ``("normal", shape, scale)``, ``("const", shape, value)`` or
``("log_linspace", shape, lo, hi)`` (the last axis spans log(lo)..log(hi),
repeated over the leading axes).  :func:`make_params` fills them in one
jitted call, each leaf from its own fold of the key, so the program and
the reference start from the very same values without either making them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and bool(x) and isinstance(x[0], str)


def _leaf(key, spec):
    kind, shape = spec[0], tuple(spec[1])
    if kind == "normal":
        return spec[2] * jax.random.normal(key, shape, jnp.float32)
    if kind == "const":
        return jnp.full(shape, spec[2], jnp.float32)
    if kind == "log_linspace":
        row = jnp.log(jnp.linspace(spec[2], spec[3], shape[-1],
                                   dtype=jnp.float32))
        return jnp.broadcast_to(row, shape)
    raise ValueError(f"unknown leaf init {kind!r}")


def make_params(key, spec):
    """The parameter tree of ``spec`` (traceable: call it under jit)."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec)
    return jax.tree.unflatten(
        treedef, [_leaf(jax.random.fold_in(key, i), s)
                  for i, s in enumerate(leaves)])


PROBES = 4


def project(leaf, key):
    """The leaf's components along ``PROBES`` random directions drawn from
    ``key`` (normal entries): each an unbiased sample of its squared norm,
    so the root mean square of the differences of two leaves' projections
    estimates the norm of their difference.  Traceable."""
    x = leaf.astype(jnp.float32)
    return jnp.stack([jnp.sum(x * jax.random.normal(
        jax.random.fold_in(key, k), x.shape, jnp.float32))
        for k in range(PROBES)])


def probe_keys(key, n_client_leaves: int, n_server_leaves: int, n: int):
    """Keys of the directions: client leaf j of client i, server leaf j."""
    ck = [[jax.random.fold_in(jax.random.fold_in(key, j), i)
           for i in range(n)] for j in range(n_client_leaves)]
    sk = [jax.random.fold_in(jax.random.fold_in(key, 1 << 20), j)
          for j in range(n_server_leaves)]
    return ck, sk


def split_stages(params, cut: int, tied: bool):
    """(client, server) in the program's layout: the client holds the
    embedding and layers [0, cut); the server the rest, the final norm and
    the output head, or its own copy of the embedding when it is tied."""
    (stack,) = params["stack"]
    client = {"embed": params["embed"],
              "stack": [jax.tree.map(lambda a: a[:cut], stack)]}
    server = {"stack": [jax.tree.map(lambda a: a[cut:], stack)],
              "rem": [], "final_norm": params["final_norm"]}
    if tied:
        server["embed"] = params["embed"]
    else:
        server["head"] = params["head"]
    return client, server


def leaf_names(tree, prefix: str):
    """``prefix/...`` path names of the leaves, in flatten order."""
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [prefix + jax.tree_util.keystr(p) for p, _ in paths]
