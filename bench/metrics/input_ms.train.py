"""Mean milliseconds per round spent making the round's rows on the host
and placing them on the device (the benchmark's ``input`` span)."""


def read(r):
    t = r.get("spans", {}).get("input") if r.get("kind") == "train" else None
    if not t:
        return None
    return 1e3 * sum(t) / len(t)
