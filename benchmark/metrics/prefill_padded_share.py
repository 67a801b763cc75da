"""Padding's share of the prefill rows of the window: 100 x padded /
(real + padded) tokens, from ``engine.prefill``'s real tokens ``C`` and
row width ``S``."""
from metrics import phase_ring


def read(result, ctx):
    rows = [p.attrs for _, inside in phase_ring.steps(result)
            for p in inside.get("engine.prefill", ())]
    if not rows:
        return None
    return 100.0 * sum(a["S"] - a["C"] for a in rows) \
        / sum(a["S"] for a in rows)
