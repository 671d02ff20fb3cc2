"""Training cells: WSSL rounds of ``make_round_fn`` driven from the seed.

Set-up builds the round's state on the device in one jitted call from the
benchmark's weights, compiles the round, and drives it through its first
three rounds on the window's own call and feed; the program's readings
for the comparison are taken there (the losses, each client's training
and validation loss, the activation bytes that crossed the cut, the first gradient from the optimizer's first
moment with its projections on random directions, the change of every
parameter after the three rounds, and the masks).  The window then runs rounds until
``seconds`` have passed, each round making its rows on the host, placing
them, calling the round and waiting for it.  Once the window has closed
and the peak memory is read, the program's state is freed and the plain
reference (``bench/reference``) runs the same three rounds.
"""

from __future__ import annotations

import gc
import importlib
import math
import statistics
import time

from common import GIB, BenchError, np_rng, program_model, prng_key

ROUNDS_CHECKED = 3


def wssl_configs(cfg: dict, traffic: dict):
    """The program's configs for this cell, checked against the files."""
    from repro.config import TrainConfig, WSSLConfig

    model = program_model(cfg)
    opt = traffic["optimizer"]
    w = WSSLConfig(num_clients=traffic["clients"],
                   participation_fraction=traffic["participation"],
                   split_layer=cfg["split_layer"],
                   importance_temp=traffic["importance_temp"],
                   importance_ema=traffic["importance_ema"])
    t = TrainConfig(rounds=opt["total_rounds"], learning_rate=opt["lr"],
                    weight_decay=opt["weight_decay"], beta1=opt["beta1"],
                    beta2=opt["beta2"], eps=opt["eps"],
                    grad_clip=opt["grad_clip"],
                    warmup_steps=opt["warmup_rounds"],
                    schedule=opt["schedule"], remat=traffic["remat"])
    return model, w, t


def worst_leaf(diff, ref, keep=None):
    """(worst, its index) of diff / max(ref, the median leaf's ref), over
    the leaves ``keep`` marks."""
    scale = statistics.median(ref)
    idx = [i for i in range(len(ref)) if keep is None or keep[i]]
    if len(diff) != len(ref) or not idx:
        return math.inf, None
    worst, at = 0.0, idx[0]
    for i in idx:
        if not (math.isfinite(diff[i]) and math.isfinite(ref[i])):
            return math.inf, i
        gap = diff[i] / max(ref[i], scale)
        if gap > worst:
            worst, at = gap, i
    return worst, at


def leaf_gap(prog, ref, keep=None):
    """Worst leaf of |norm_prog - norm_ref| / max(norm_ref, median)."""
    return worst_leaf([abs(p - r) for p, r in zip(prog, ref)], ref, keep)


def probe_gap(prog, ref, norms):
    """(worst, index) over leaves of the root mean square difference of the
    projections, against max(the leaf's reference norm, the median's)."""
    diff = [math.sqrt(sum((a - b) ** 2 for a, b in zip(p, r)) / len(p))
            for p, r in zip(prog, ref)]
    return worst_leaf(diff, norms)


def relative_loss_gap(prog, ref, masks):
    """Worst round of the largest gap between the clients' losses, each
    taken about the mean of the clients the mask selects, over that mean
    of the reference's: what decides importance and selection, with the
    offset that every client shares taken out."""
    worst = 0.0
    for p, r, m in zip(prog, ref, masks):
        sel = [i for i in range(len(m)) if m[i] > 0]
        if not sel:
            continue
        mp = sum(p[i] for i in sel) / len(sel)
        mr = sum(r[i] for i in sel) / len(sel)
        if not mr:
            return math.inf
        for i in sel:
            gap = abs((p[i] - mp) - (r[i] - mr)) / abs(mr)
            if not math.isfinite(gap):
                return math.inf
            worst = max(worst, gap)
    return worst


def compare(prog: dict, ref: dict, limits: dict, moved_floor: float):
    """The numbers compared, each beside its limit; the limits file names
    those that decide ``correct``, the others are printed alone."""
    loss = max((float(abs(p - r) / abs(r)) if math.isfinite(p) else math.inf)
               for p, r in zip(prog["losses"], ref["losses"]))
    gp = prog["grad_norms"]["client"] + prog["grad_norms"]["server"]
    gr = ref["grad_norms"]["client"] + ref["grad_norms"]["server"]
    dp = prog["change_norms"]["client"] + prog["change_norms"]["server"]
    dr = ref["change_norms"]["client"] + ref["change_norms"]["server"]
    g_med = statistics.median(gr)
    moved = [g >= moved_floor * g_med for g in gr]
    names = prog.get("leaves", [])
    pp = prog["grad_probes"]["client"] + prog["grad_probes"]["server"]
    pr = ref["grad_probes"]["client"] + ref["grad_probes"]["server"]
    cut = max(abs(p - r) / r for p, r in zip(prog["cut_bytes"],
                                             ref["cut_bytes"]))
    every = [[1.0] * len(v) for v in prog["val_losses"]]
    values = {"loss_rel_gap": (loss, None),
              "client_loss_gap": (relative_loss_gap(
                  prog["client_losses"], ref["client_losses"],
                  prog["masks"]), None),
              "val_loss_gap": (relative_loss_gap(
                  prog["val_losses"], ref["val_losses"], every), None),
              "cut_bytes_gap": (cut, None),
              "grad_norm_gap": leaf_gap(gp, gr),
              "grad_dir_gap": probe_gap(pp, pr, gr),
              "change_norm_gap": leaf_gap(dp, dr, moved),
              # the program's masks against the reference's draw; a
              # reference put in the program's place draws its own
              "select_gap": (max(prog.get("select_gaps",
                                          ref["select_gaps"])), None),
              "unmoved_leaves": (float(len(moved) - sum(moved)), None)}
    out = []
    for k, (v, i) in values.items():
        lim = limits.get(k)
        out.append({"name": k, "value": v, "limit": lim,
                    "ok": lim is None or (math.isfinite(v) and v <= lim),
                    "leaf": names[i] if i is not None and i < len(names)
                    else None})
    return out


class TrainCell:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from repro.core.round import WSSLState, abstract_state, make_round_fn
        from repro.optim.optimizers import adamw_init

        import weights

        self.ctx, self.jax = ctx, jax
        cfg, tr = ctx.config, ctx.traffic
        self.fam = importlib.import_module(f"reference.{cfg['family']}")
        self.model, self.w, self.t = wssl_configs(cfg, tr)
        self.spec = self.fam.param_spec(cfg["model"])
        n, cut = tr["clients"], cfg["split_layer"]
        tied = cfg["model"]["tie_embeddings"]

        def stages(key):
            return weights.split_stages(
                weights.make_params(jax.random.fold_in(key, 0), self.spec),
                cut, tied)

        def build(key):
            client, server = stages(key)
            stack = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), client)
            return WSSLState(
                client_stack=stack, server_params=server, edge_stages=(),
                opt_client=adamw_init(stack), opt_server=adamw_init(server),
                opt_edge=(), importance=jnp.full((n,), 1.0 / n, jnp.float32),
                round_index=jnp.zeros((), jnp.int32),
                rng=jax.random.fold_in(key, 1), ef_residual=())

        def norms(tree):
            return [jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                    for l in jax.tree.leaves(tree)]

        def change(stack, server, key):
            client0, server0 = stages(key)
            d = jax.tree.map(lambda a, b: a - b[None], stack, client0)
            return norms(d), norms(jax.tree.map(jnp.subtract, server,
                                                server0))

        self.stages = jax.jit(stages)
        self.build = jax.jit(build)

        def first_grads(state, key):
            cm = jax.tree.leaves(state.opt_client.m)
            sm = jax.tree.leaves(state.opt_server.m)
            ck, sk = weights.probe_keys(key, len(cm), len(sm), n)
            cproj = [sum(weights.project(l[i], ck[j][i]) for i in range(n))
                     for j, l in enumerate(cm)]
            sproj = [weights.project(l, sk[j]) for j, l in enumerate(sm)]
            return norms(cm), norms(sm), cproj, sproj

        self.first_grads = jax.jit(first_grads)
        self.change = jax.jit(change)
        want = jax.tree.structure(abstract_state(self.model, self.w,
                                                 self.t)[0])
        got = jax.eval_shape(build, jax.random.PRNGKey(0))
        if jax.tree.structure(got) != want:
            raise BenchError("the program's round state no longer has the "
                             "layout the benchmark builds")
        self.round_fn = make_round_fn(self.model, self.w, self.t,
                                      impl=tr["impl"], donate=True)
        self.gen_mod = importlib.import_module(
            f"generators.{tr['generator']}")
        self.weights = weights
        self.refs = {}
        self.seed(ctx.seed)

    def seed(self, seed):
        """Rows for ``seed`` (the weights follow from the key)."""
        tr = self.ctx.traffic
        self.gen = self.gen_mod.LMTokens(
            self.ctx.config["model"]["vocab_size"], seed, np_rng)
        self.round_batch = lambda r: self.gen_mod.round_batch(self.gen, tr, r)
        self.val = self.gen_mod.val_batch(self.gen, tr)

    def probe_key(self, key):
        return self.jax.random.fold_in(key, 99)

    # -- the timed path ---------------------------------------------------

    def feed(self, r):
        with self.ctx.spans("input"):
            batch = self.jax.device_put(self.round_batch(r))
        return batch

    def step(self, state, batch, val):
        with self.ctx.spans("dispatch"):
            state, m = self.round_fn(state, batch, val)
        with self.ctx.spans("block"):
            self.jax.block_until_ready((state, m))
        return state, m

    # -- a run ------------------------------------------------------------

    def checked_rounds(self, seed):
        """State built from ``seed`` and driven through the first rounds
        -> (state, the program's readings, key, placed validation rows)."""
        jax, tr = self.jax, self.ctx.traffic
        self.seed(seed)
        key = prng_key(jax, seed)
        state = self.build(key)
        val = jax.device_put(self.val)
        losses, masks, cut, client_l, val_l = [], [], [], [], []
        for r in range(ROUNDS_CHECKED):
            state, m = self.step(state, self.feed(r), val)
            losses.append(m.loss)
            masks.append(m.mask)
            cut.append(m.bytes_up)
            client_l.append(m.per_client_loss)
            val_l.append(m.val_loss)
            if r == 0:
                g1 = self.first_grads(state, self.probe_key(key))
        d3 = self.change(state.client_stack, state.server_params, key)
        b1 = tr["optimizer"]["beta1"]
        prog = {"leaves": (self.weights.leaf_names(state.client_stack,
                                                   "client")
                           + self.weights.leaf_names(state.server_params,
                                                     "server")),
                "losses": [float(x) for x in losses],
                "cut_bytes": [float(x) for x in cut],
                "masks": [jax.device_get(x).tolist() for x in masks],
                "client_losses": [jax.device_get(x).tolist()
                                  for x in client_l],
                "val_losses": [jax.device_get(x).tolist() for x in val_l],
                "grad_norms": {k: [float(x) / (1 - b1) for x in v]
                               for k, v in zip(("client", "server"), g1)},
                "grad_probes": {k: [[float(x) / (1 - b1) for x in p]
                                    for p in v]
                                for k, v in zip(("client", "server"),
                                                g1[2:])},
                "change_norms": {k: [float(x) for x in v]
                                 for k, v in zip(("client", "server"), d3)}}
        return state, prog, key, val

    def run(self):
        ctx, tr = self.ctx, self.ctx.traffic
        state, prog, key, val = self.checked_rounds(ctx.seed)
        ctx.spans.times.clear()
        ctx.setup_done()

        window_losses, r = [], ROUNDS_CHECKED
        before = ctx.compiles.snapshot()
        with ctx.window():
            t0 = time.perf_counter()
            while True:
                state, m = self.step(state, self.feed(r), val)
                window_losses.append(m.loss)
                r += 1
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            window_s = time.perf_counter() - t0
        after = ctx.compiles.snapshot()
        rounds = r - ROUNDS_CHECKED
        peak = ctx.peak_bytes()
        all_losses = prog["losses"] + [float(x) for x in window_losses]
        del state, m, window_losses
        gc.collect()

        t0 = time.perf_counter()
        ref = self.reference(key, prog["masks"])
        ctx.note(f"reference: {time.perf_counter() - t0:.3f} s")
        checks = [c for c in compare(prog, ref, ctx.limits,
                                     tr["moved_floor"])
                  if c["limit"] is not None]
        flops = importlib.import_module(f"flops.{ctx.config['family']}")
        return {
            "attempted": len(all_losses),
            "failed": sum(not math.isfinite(x) for x in all_losses),
            "checks": checks,
            "peak_bytes": peak,
            "e2e": {"round_s": window_s / rounds,
                    "peak_hbm_gib": peak / GIB},
            "readings": {
                "kind": "train", "rounds": rounds, "window_s": window_s,
                "spans": dict(ctx.spans.times),
                "compiles_in_window": (after["backend_compiles"]
                                       - before["backend_compiles"]),
                "model_flops_per_round": flops.train_flops(
                    ctx.config["model"], tr),
            },
            "program": prog, "reference": ref,
        }

    def reference(self, key, masks, round_to=None, fault=None):
        """The plain reference's three rounds from the same weights."""
        from reference.wssl_round import Reference

        jax, tr = self.jax, self.ctx.traffic
        batches = [self.round_batch(r) for r in range(ROUNDS_CHECKED)]
        with jax.default_matmul_precision("highest"):
            if (round_to, fault) not in self.refs:
                self.refs[round_to, fault] = Reference(
                    self.fam, self.ctx.config["model"], tr,
                    round_to=round_to, fault=fault)
            return self.refs[round_to, fault].run(
                lambda: self.stages(key), batches, self.val,
                jax.random.fold_in(key, 1), masks, self.probe_key(key))


CELL = TrainCell
