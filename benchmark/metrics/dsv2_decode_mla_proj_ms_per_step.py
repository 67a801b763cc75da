"""Device time of the latent attention's projections in one decode
step: self time of ``jit__decode``'s instructions under ``mla_q``,
``mla_kv_down``, ``mla_absorb`` or ``mla_out``, mean over the traced
runs (``scope_time.py``).  The kernel's own scope ``attn_latent`` is
left out: ``latent_decode_attn_roofline`` has it."""
from metrics import scope_time


def read(result, ctx):
    return scope_time.read_group(result, ctx, "decode",
                                 scope_time.DSV2_DECODE, "mla_proj")
