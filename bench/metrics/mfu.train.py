"""Model FLOP utilisation of a training cell: the selected clients'
forward and backward model FLOPs per round (``bench/flops``, no
recomputation, no validation) times the rounds of the window, over the
window and the chips' bf16 peak (``bench/peaks.json``), in percent."""


def read(r):
    if r.get("kind") != "train" or not r.get("rounds"):
        return None
    peak = r["peak"]["bf16_flops_per_s"] * r["chips"]
    return (100.0 * r["model_flops_per_round"] * r["rounds"]
            / r["window_s"] / peak)
