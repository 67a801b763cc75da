"""How full the grouped expert product's row tiles are: the (token,
expert) pairs the held experts took (``engine.decode``'s
``moe_pairs_held``) over the rows the kernels multiplied
(``moe_rows_tiled``: each layer's active row tiles times the tile),
x 100, mean over the window's decode steps.  100 would be tiles that
hold nothing but real pairs; a program without the counter reads
nothing."""
from metrics import phase_ring


def read(result, ctx):
    return phase_ring.mean(
        100.0 * d.attrs["moe_pairs_held"] / d.attrs["moe_rows_tiled"]
        for _, inside in phase_ring.steps(result)
        for d in inside.get("engine.decode", ())
        if d.attrs.get("moe_rows_tiled"))
