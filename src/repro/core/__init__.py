"""WSSL core: the paper's contribution.

* wssl.py     — Algorithm 1 (importance, selection, weighted sampling),
                the Algorithm 2 aggregation coefficients, and the
                staleness discounts for bounded-staleness async rounds.
* aggregation.py — the pluggable robust-aggregation registry (importance /
                uniform / trimmed_mean / median / krum / multi_krum) every
                round variant dispatches Algorithm 2 step 5 through.
* split.py    — the two-phase split fwd/bwd protocol (≡ end-to-end grad).
* round.py    — one fused WSSL round body for the transformer stack.
* async_round.py — the bounded-staleness variant: round deadline,
                stale-update buffer, staleness-weighted aggregation, as
                steps added to round.py's body (deadline=inf ≡ sync).
* paper_loop.py — paper-scale WSSL trainer (gait FFN / ResNet-18).
* protocol.py — communication accounting (incl. staleness columns).
* fairness.py — participation / accuracy fairness metrics.
"""

from repro.core import aggregation, fairness, protocol, split, wssl  # noqa: F401
