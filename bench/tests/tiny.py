"""Cells at a size the CPU runs in seconds, for the benchmark's tests:
the same drivers, the configuration files' model keys shrunk."""

import copy
import json

from common import BENCH

TINY_MODEL = {
    "mamba2-370m": {"num_layers": 3, "d_model": 64, "vocab_size": 512,
                    "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 16},
    "musicgen-medium-12l": {"num_layers": 3, "d_model": 64,
                            "num_heads": 4, "num_kv_heads": 4,
                            "head_dim": 16, "d_ff": 128,
                            "vocab_size": 256},
}
TINY_TRAFFIC = {
    "train_rounds": {"rows_per_client": 2, "seq": 64, "val_rows": 2},
}


def cell(workload: str, seed: int = 5, seconds: float = 1.0,
         limits: dict = None):
    """A driver context for ``workload`` (``<config>.<traffic>``) at the
    tiny size on the CPU."""
    import jax
    from run import Context

    config = next(c for c in TINY_MODEL if workload.startswith(c + "."))
    wl = {"name": workload, "config": config,
          "traffic": workload[len(config) + 1:], "chips": 1}
    cfg = json.loads((BENCH / "configs" / f"{wl['config']}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(TINY_MODEL[wl["config"]])
    cfg["split_layer"] = 1
    tr = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json").read_text())
    tr.update(TINY_TRAFFIC[tr["driver"]])
    path = BENCH / "limits" / f"{workload}.json"
    lim = limits or json.loads(path.read_text())
    return Context(jax, jax.devices()[:1], wl, cfg, tr, lim, seed, seconds,
                   0)
