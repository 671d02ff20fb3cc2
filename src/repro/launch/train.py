"""WSSL training launcher.

Runs real WSSL rounds (Algorithm 1 + 2) over the transformer stack with
synthetic LM data.  On CPU use ``--reduced``; on a TPU pod the same driver
runs the full config under the production mesh (``--mesh prod``).

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-12b --reduced \
      --clients 4 --rounds 10 --seq-len 128 --batch-per-client 2
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.config import TrainConfig, WSSLConfig, get_arch, reduced
from repro.core.round import init_state, make_round_fn
from repro.data.synthetic import lm_batch
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-per-client", type=int, default=2)
    ap.add_argument("--val-batch", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--participation", type=float, default=0.5)
    ap.add_argument("--impl", default="dense")
    ap.add_argument("--client-chunk", type=int, default=None,
                    help="scan the per-client forward/backward in chunks "
                         "of this many clients (must divide --clients); "
                         "caps activation memory at O(chunk)")
    ap.add_argument("--fused-adam", action="store_true",
                    help="fused masked-AdamW Pallas kernel instead of the "
                         "unfused tree.map optimizer chain")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    wssl_cfg = WSSLConfig(num_clients=args.clients,
                          participation_fraction=args.participation)
    train_cfg = TrainConfig(rounds=args.rounds, learning_rate=args.lr,
                            remat=not args.reduced,
                            client_chunk=args.client_chunk,
                            fused_adam=args.fused_adam)

    state, _ = init_state(jax.random.PRNGKey(args.seed), cfg, wssl_cfg,
                          train_cfg)
    # donate=True: the incoming state aliases the round's output, so one
    # copy of the per-client stacks + optimizer slots is live at peak
    round_fn = make_round_fn(cfg, wssl_cfg, train_cfg, impl=args.impl,
                             donate=True)

    n, b, s = args.clients, args.batch_per_client, args.seq_len
    vd = lm_batch(args.val_batch, s, cfg.vocab_size, seed=10_000)
    val = {"tokens": jnp.asarray(vd["tokens"]),
           "labels": jnp.asarray(vd["labels"])}

    history = []
    for r in range(args.rounds):
        d = lm_batch(n * b, s, cfg.vocab_size, seed=args.seed * 1000 + r)
        batch = {"tokens": jnp.asarray(d["tokens"]).reshape(n, b, s),
                 "labels": jnp.asarray(d["labels"]).reshape(n, b, s)}
        t0 = time.time()
        state, m = round_fn(state, batch, val)
        jax.block_until_ready(state)   # dt covers the round, not dispatch
        dt = time.time() - t0
        rec = {"round": r, "loss": float(m.loss), "dt_s": dt,
               "selected": int(m.mask.sum()),
               "mean_val_loss": float(m.val_loss.mean()),
               "importance": np.asarray(m.importance).round(4).tolist(),
               "bytes_up_MB": float(m.bytes_up) / 1e6}
        history.append(rec)
        print(f"round {r:3d}  loss={rec['loss']:.4f}  "
              f"val={rec['mean_val_loss']:.4f}  sel={rec['selected']}  "
              f"up={rec['bytes_up_MB']:.1f}MB  {dt:.1f}s")

    if args.checkpoint:
        save_checkpoint(args.checkpoint,
                        {"client_stack": state.client_stack,
                         "server": state.server_params},
                        metadata={"arch": args.arch, "rounds": args.rounds})
        print("checkpoint ->", args.checkpoint)
    if args.log:
        with open(args.log, "w") as f:
            json.dump(history, f, indent=2)


if __name__ == "__main__":
    main()
