"""How much of the latent page walk the decode rows do NOT share: the
block fetches the latent kernel issued (``engine.decode``'s
``latent_blocks_fetched``) over the blocks its rows had to see
(``latent_blocks_walked``), summed over the window's decode spans; 100
where no two rows read the same pages."""
from metrics import phase_ring


def read(result, ctx):
    spans = [d.attrs for _, inside in phase_ring.steps(result)
             for d in inside.get("engine.decode", ())
             if d.attrs and d.attrs.get("latent_blocks_walked")]
    walked = sum(a["latent_blocks_walked"] for a in spans)
    if not walked:
        return None
    return 100.0 * sum(a["latent_blocks_fetched"] for a in spans) / walked
