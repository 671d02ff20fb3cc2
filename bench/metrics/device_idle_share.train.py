"""Share of the traced window of a training cell in which no operation
ran on the device (``bench/trace_reduce.py``), in percent."""


def read(r):
    t = r.get("trace") or {}
    if r.get("kind") != "train" or t.get("idle_share") is None:
        return None
    return 100.0 * t["idle_share"]
