"""Device time of one decode step outside the named parts: self time of
``jit__decode``'s instructions under none of the latent projections'
scopes, the MLPs' scopes or ``attn_latent`` (norms, residuals, ``head``,
``embedding``, the argmax, copies and layout changes between the parts,
``latent_walk_tiles``), mean over the traced runs (``scope_time.py``).
With the other two sums and ``attn_latent`` it adds up to the run."""
from metrics import scope_time


def read(result, ctx):
    return scope_time.read_group(result, ctx, "decode",
                                 scope_time.DSV2_DECODE, scope_time.REST)
