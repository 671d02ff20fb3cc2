"""Backend compiles during the measured window of a training cell (JAX's
``/jax/core/compile/backend_compile_duration`` events; 0 when every
shape was warmed in set-up)."""


def read(r):
    if r.get("kind") != "train":
        return None
    return r["compiles_in_window"]
