"""Plain WSSL rounds (the paper's Algorithms 1 and 2, as the program's
configuration runs them), one client at a time, in float32.

Round r, from the selection key ``rng``:
  rng, rng_sel = split(rng)
  selection: Gumbel top-k of log(importance) + gumbel(rng_sel); round 0
             selects every client.  The program's own mask is followed,
             and ``select_gap`` says by how much the reference's scores
             would have chosen otherwise (0 where they agree).
  loss:      sum_i w_i * mean-token-NLL_i over the selected clients, with
             w = importance * mask / sum(importance * mask)
  step:      gradients clipped to a global norm (clients together, server
             alone), then AdamW with the schedule; unselected clients keep
             their parameters and moments
  score:     each client's updated stage with the updated server on the
             server's validation rows -> importance =
             normalize(ema * prev + (1 - ema) * softmax(-val_loss / T))
  aggregate: the selected clients' stages averaged with weights
             importance * mask / sum, and sent back to every client.

It returns, for the comparison: the three losses, each selected
client's training loss and every client's validation loss in each round,
the activation bytes that crossed the cut in each round, each leaf's norm
of the first gradient as the optimizer takes it and its projections on
the benchmark's random directions (``weights.project``), each leaf's norm
of the change after the three rounds, and the selection gaps.

``fault`` plants a fault, for the readings the limits are set from and
for the benchmark's own tests of the comparison: ``"half_batch"`` forwards
every row but takes each client's loss over half of its rows (over half of
the positions where a client has one row); ``"first_k"`` trains the first
k clients in rounds after the first instead of those the masks name.
Nothing here imports the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.plain import Precision
from weights import probe_keys, project

ROUNDS = 3


def schedule(opt, step):
    """Warm-up then cosine to a tenth (the configuration's schedule)."""
    warm = min(1.0, (step + 1) / max(opt["warmup_rounds"], 1))
    frac = min(max((step - opt["warmup_rounds"])
                   / max(opt["total_rounds"] - opt["warmup_rounds"], 1),
                   0.0), 1.0)
    return opt["lr"] * warm * (0.1 + 0.45 * (1 + math.cos(math.pi * frac)))


def _norm(tree):
    return math.sqrt(sum(float(jnp.sum(jnp.square(l)))
                         for l in jax.tree.leaves(tree)))


def _leaf_sq(tree):
    return [jnp.sum(jnp.square(l)) for l in jax.tree.leaves(tree)]


class Reference:
    def __init__(self, fam, m, traffic, round_to=None, fault=None):
        self.fam, self.m, self.tr, self.fault = fam, m, traffic, fault
        pr = Precision(round_to)
        opt = traffic["optimizer"]
        b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], \
            opt["weight_decay"]

        def counted(acts, labels):
            """The rows (or positions) whose loss counts."""
            if fault != "half_batch":
                return acts, labels
            if labels.shape[0] > 1:
                h = labels.shape[0] // 2
                return acts[:h], labels[:h]
            h = labels.shape[1] // 2
            return acts[:, :h], labels[:, :h]

        self.act_bytes = m["d_model"] * jnp.dtype(m["dtype"]).itemsize

        def client_fwd(cp, toks):
            x = fam.embed(cp, toks, m)
            return fam.stack_forward(cp["stack"][0], x, m, pr)

        def server_loss(sp, acts, labels):
            h = fam.stack_forward(sp["stack"][0], acts, m, pr)
            return fam.head_nll_sum(sp, h, labels, m, pr) / labels.size

        def train_client(cp, sp, toks, labels, w):
            acts, back = jax.vjp(lambda c: client_fwd(c, toks), cp)
            loss, (gs, ga) = jax.value_and_grad(
                lambda s, a: server_loss(s, *counted(a, labels)), (0, 1))(
                    sp, acts)
            (gc,) = back(w * ga)
            return loss, jax.tree.map(lambda g: w * g, gs), gc

        def val_loss(cp, sp, vt, vl):
            return server_loss(sp, client_fwd(cp, vt), vl)

        def adam(p, g, mo, v, scale, lr, t):
            def one(p, g, mo, v):
                g = g * scale
                mo = b1 * mo + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                mh, vh = mo / (1 - b1 ** t), v / (1 - b2 ** t)
                return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p), mo, v
            out = jax.tree.map(one, p, g, mo, v)
            pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                          is_leaf=lambda o: isinstance(
                                              o, tuple))
            return pick(0), pick(1), pick(2)

        self.train_client = jax.jit(train_client)
        self.val_loss = jax.jit(val_loss)
        # the gradients and moments are dead once stepped: donated, so
        # that a step holds one copy of the server's state, not two; a
        # client's parameters may be shared (every client starts from one
        # stage) and are not
        self.adam = jax.jit(adam, donate_argnums=(1, 2, 3))
        self.adam_server = jax.jit(adam, donate_argnums=(0, 1, 2, 3))
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self.wsum = jax.jit(lambda trees, w: jax.tree.map(
            lambda *ls: sum(wi * l for wi, l in zip(w, ls)), *trees))
        self.sq = jax.jit(_leaf_sq)
        self.proj = jax.jit(lambda tree, keys: [
            project(l, k) for l, k in zip(jax.tree.leaves(tree), keys)])
        self.dsq = jax.jit(lambda a, b: _leaf_sq(
            jax.tree.map(jnp.subtract, a, b)))

    def run(self, stages, batches, val, rng, masks, probe_key):
        """Three rounds from the weights ``stages()`` makes (called again
        at the end for the change, so that no copy is held meanwhile);
        ``masks`` are the program's (ROUNDS, N) selections; ``probe_key``
        draws the directions the first gradient is projected on."""
        tr, opt = self.tr, self.tr["optimizer"]
        n = tr["clients"]
        zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
        client0, server0 = stages()
        shapes = [len(jax.tree.leaves(client0)),
                  len(jax.tree.leaves(server0))]
        clients = [client0] * n
        mc = [(zeros(client0), zeros(client0)) for _ in range(n)]
        server, ms = server0, (zeros(server0), zeros(server0))
        del client0, server0
        imp = np.full((n,), 1.0 / n)
        vt, vl = jnp.asarray(val["tokens"]), jnp.asarray(val["labels"])
        losses, gaps, cut_bytes, g1 = [], [], [], None
        client_losses, val_losses, used = [], [], []
        for r in range(ROUNDS):
            rng, rng_sel = jax.random.split(rng)
            mask = np.asarray(masks[r], np.float64)
            if self.fault == "first_k" and r > 0:
                mask = (np.arange(n) < int((mask > 0).sum())).astype(
                    np.float64)
            used.append(mask.tolist())
            sel = mask > 0
            if r == 0:
                gaps.append(0.0 if sel.all() else math.inf)
            else:
                score = (np.log(np.maximum(imp, 1e-12))
                         + np.asarray(jax.random.gumbel(rng_sel, (n,)),
                                      np.float64))
                k = int(sel.sum())
                ok = (k == max(int(round(n * tr["participation"])), 1)
                      and k < n)
                gaps.append(max(0.0, float(score[~sel].max()
                                           - score[sel].min()))
                            if ok else math.inf)
            w = imp * mask / (imp * mask).sum()
            loss, gs, gc = 0.0, None, [None] * n
            per_client = [0.0] * n
            toks = jnp.asarray(batches[r]["tokens"])
            labs = jnp.asarray(batches[r]["labels"])
            cut_bytes.append(sum(toks[i].size for i in np.flatnonzero(sel))
                             * self.act_bytes)
            for i in np.flatnonzero(sel):
                li, gsi, gc[i] = self.train_client(
                    clients[i], server, toks[i], labs[i], jnp.float32(w[i]))
                per_client[i] = float(li)
                loss += w[i] * per_client[i]
                gs = gsi if gs is None else self.add(gs, gsi)
            losses.append(loss)
            client_losses.append(per_client)
            clip = opt["grad_clip"]
            c_norm = math.sqrt(sum(_norm(g) ** 2 for g in gc if g is not None))
            c_scale = min(1.0, clip / max(c_norm, 1e-12))
            s_scale = min(1.0, clip / max(_norm(gs), 1e-12))
            if r == 0:
                g1 = {"client": [math.sqrt(sum(float(x) for x in col))
                                 * c_scale for col in zip(
                                     *[self.sq(g) for g in gc
                                       if g is not None])],
                      "server": [math.sqrt(float(x)) * s_scale
                                 for x in self.sq(gs)]}
                ck, sk = probe_keys(probe_key, *shapes, n)
                cp = None
                for i in np.flatnonzero(sel):
                    pi = self.proj(gc[i], [row[i] for row in ck])
                    cp = pi if cp is None else [a + b for a, b in zip(cp, pi)]
                probes = {"client": [[float(x) * c_scale for x in p]
                                     for p in cp],
                          "server": [[float(x) * s_scale for x in p]
                                     for p in self.proj(gs, sk)]}
            lr, t = jnp.float32(schedule(opt, r)), jnp.float32(r + 1)
            for i in np.flatnonzero(sel):
                clients[i], m_i, v_i = self.adam(
                    clients[i], gc[i], *mc[i], jnp.float32(c_scale), lr, t)
                mc[i], gc[i] = (m_i, v_i), None
            server, m_s, v_s = self.adam_server(server, gs, *ms,
                                         jnp.float32(s_scale), lr, t)
            ms = (m_s, v_s)
            del gc, gs
            val_l = np.array([float(self.val_loss(c, server, vt, vl))
                              for c in clients])
            val_losses.append(val_l.tolist())
            z = -val_l / tr["importance_temp"]
            soft = np.exp(z - z.max())
            soft /= soft.sum()
            imp = tr["importance_ema"] * imp + (1 - tr["importance_ema"]) * soft
            imp = imp / max(imp.sum(), 1e-12)
            coef = imp * mask / (imp * mask).sum()
            picked = np.flatnonzero(sel)
            glob = self.wsum([clients[i] for i in picked],
                             [jnp.float32(coef[i]) for i in picked])
            clients = [glob] * n
        del mc, ms
        client0, server0 = stages()
        delta = {"client": [math.sqrt(n * float(x))
                            for x in self.dsq(clients[0], client0)],
                 "server": [math.sqrt(float(x))
                            for x in self.dsq(server, server0)]}
        return {"losses": losses, "client_losses": client_losses,
                "val_losses": val_losses, "masks": used,
                "grad_norms": g1, "grad_probes": probes,
                "change_norms": delta, "select_gaps": gaps,
                "cut_bytes": cut_bytes}
