"""How much of the window's prompts rode in on shared pages: the sum of
``shared`` over the sum of the context lengths (``ctx``) of the
window's ``engine.prefill`` phases that finished a prompt."""
from metrics import phase_ring


def read(result, ctx):
    done = [p.attrs for _, inside in phase_ring.steps(result)
            for p in inside.get("engine.prefill", ())
            if p.attrs and "ctx" in p.attrs and "shared" in p.attrs]
    total = sum(a["ctx"] for a in done)
    if not total:
        return None
    return 100.0 * sum(a["shared"] for a in done) / total
