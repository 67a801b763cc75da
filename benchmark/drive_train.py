"""The window driver of ``kind: train`` configurations.

Set-up builds ONE object, the compiled ZeRO train step with its state
(``build_flagship_train_step``), puts the benchmark's seeded weights in
it, drives it through its first three steps on three different seeded
batches, through the window's own call and feed, and hands that same
object to the window.  After the window the plain reference follows
the same three steps from the same weights, and each step's loss, the
first gradient's norm per leaf (from the optimizer's first moment after
step 1) and the parameters' change per leaf after step 3 are compared.
"""

from __future__ import annotations

import importlib
import math
import statistics
import time

import numpy as np

import harness
import weights

CHECKED_STEPS = 3


def _paths(tree, is_leaf=None) -> dict:
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): x
            for path, x in leaves}


def build(ctx):
    """(FlagshipSetup without its weights, params, layout, ref module)."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.transformer.testing import build_flagship_train_step
    from apex_tpu.transformer.testing.standalone_gpt import GPTConfig

    cfg = ctx.config
    gcfg = GPTConfig(**cfg["model"])
    devices = jax.devices()[:cfg["chips"]]
    fs = build_flagship_train_step(
        gcfg, devices=devices, mesh_shape=cfg.get("mesh"), **cfg["builder"])
    ref = importlib.import_module("reference." + cfg["reference"])
    layout = ref.param_layout(cfg["model"])
    want = {p: tuple(s[0])
            for p, s in _paths(layout, weights.is_spec).items()}
    have = {p: tuple(a.shape) for p, a in _paths(fs.params).items()}
    if want != have:
        raise RuntimeError(
            "the program's parameter tree is not the reference's layout: "
            f"{sorted(set(want.items()) ^ set(have.items()))[:6]}")
    sharding = jax.tree_util.tree_map(lambda a: a.sharding, fs.params)
    dtype = jnp.dtype(cfg["state_dtypes"]["params"])
    fs = fs._replace(params=None)          # the program's own weights go
    make = lambda: weights.make(layout, ctx.seed, dtype, sharding)
    return fs, make, ref


def make_batches(ctx, fs, n: int):
    """``n`` seeded (tokens, labels) batches, made on the device in one
    call; labels are the tokens shifted left by one."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = fs.mesh.shape["data"]
    b = ctx.mix["batch_per_chip"] * dp
    s = ctx.mix["seq"]
    vocab = ctx.config["model"]["vocab_size"]
    sharding = NamedSharding(fs.mesh, P("data"))

    def build(key):
        tok = jax.random.randint(key, (n, b, s), 0, vocab, jnp.int32)
        lab = jnp.roll(tok, -1, axis=-1)
        return tuple(jnp.unstack(tok)), tuple(jnp.unstack(lab))

    key = jax.random.fold_in(jax.random.PRNGKey(ctx.seed & 0x7FFFFFFF),
                             0x7A11 + (ctx.seed >> 31))
    toks, labs = jax.jit(build, out_shardings=sharding)(key)
    return list(zip(toks, labs))


def first_gradient(fs, b1: float, ref):
    """A jitted reader of the optimizer's state: the first gradient as
    the optimizer got it, from the first moment after one step
    (``m1 = (1 - b1) * g1``), as the reference's ``column_squares``
    leaf by leaf."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.multi_tensor.flat import unflatten

    schema = fs.schema

    def read(exp_avg):
        flat = exp_avg.reshape(-1)[:schema.total].astype(jnp.float32)
        tree = unflatten(flat / (1.0 - b1), schema, dtype=jnp.float32)
        return jax.tree_util.tree_map(ref.column_squares, tree)

    return jax.jit(read)


def first_steps(ctx, fs, params, make_weights, batches, ref):
    """The three checked steps, through the window's own call and
    feed.  Returns (params, opt_state, readings)."""
    b1 = ctx.config["optimizer"]["betas"][0]
    opt_state = fs.opt_state
    read_gradient = first_gradient(fs, b1, ref)
    losses, grad_sq = [], None
    for tokens, labels in batches[:CHECKED_STEPS]:
        params, opt_state, loss = fs.step(params, opt_state, tokens, labels)
        losses.append(float(loss))
        if grad_sq is None:
            grad_sq = ref.by_path(read_gradient(opt_state.exp_avg))
    start = make_weights()
    delta_sq = ref.by_path(ref.change_squares(params, start))
    del start
    return params, opt_state, {"losses": losses, "grad_sq": grad_sq,
                               "delta_sq": delta_sq}


STILL = 1e-3


def moving_columns(ref_grad_sq: dict) -> dict:
    """``{path: bool mask}`` of the parts of each leaf that the
    reference's gradient moves.  A column (an index of a leaf's last
    axis) whose gradient norm is under a thousandth of the leaf's
    median column, and every column of a leaf whose norm is under a
    thousandth of the median leaf's, has a gradient that is nought to
    rounding (the key third of a QKV bias under softmax): it moves
    under Adam by round-off alone and is left out of the change."""
    norms = {p: float(np.sqrt(v.sum())) for p, v in ref_grad_sq.items()}
    leaf_floor = STILL * statistics.median(norms.values())
    out = {}
    for path, sq in ref_grad_sq.items():
        col = np.sqrt(sq)
        out[path] = (col >= STILL * np.median(col)) & \
            (norms[path] >= leaf_floor)
    return out


def worst_leaf_gap(got_sq: dict, want_sq: dict, keep=None) -> tuple:
    """The widest gap between the program's norm and the reference's
    over the leaves (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  ``keep`` masks the columns that count.  Returns
    (gap, leaf)."""
    def norm(sq, path):
        return float(np.sqrt(sq[path][keep[path]].sum() if keep
                             else sq[path].sum()))

    want = {p: norm(want_sq, p) for p in want_sq}
    floor = statistics.median(want.values())
    worst, where = 0.0, ""
    for path, ref_norm in want.items():
        gap = abs(norm(got_sq, path) - ref_norm) / max(ref_norm, floor)
        if not gap <= worst:               # a NaN is the worst
            worst, where = gap, path
    return worst, where


def compare(got: dict, want: dict, limits: dict) -> list:
    checks = []
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        checks.append(harness.Check(
            f"loss{i}_gap", abs(a - b) / abs(b), limits["loss_gap"]))
    gap, _ = worst_leaf_gap(got["grad_sq"], want["grad_sq"])
    checks.append(harness.Check("grad_norm_gap", gap,
                                limits["grad_norm_gap"]))
    gap, _ = worst_leaf_gap(got["delta_sq"], want["delta_sq"],
                            keep=moving_columns(want["grad_sq"]))
    checks.append(harness.Check("delta_norm_gap", gap,
                                limits["delta_norm_gap"]))
    return checks


def reference_readings(ctx, ref, make_weights, batches, cast_name="exact",
                       half_batch=False):
    """The plain reference (or, with ``cast_name``, the control; with
    ``half_batch``, the fault that leaves half of the batch out) over
    the checked steps, from the same weights and batches."""
    import jax

    one = jax.devices()[0]
    feed = []
    for tokens, labels in batches[:CHECKED_STEPS]:
        tokens, labels = jax.device_put((tokens, labels), one)
        if half_batch:
            tokens, labels = (tokens[:tokens.shape[0] // 2],
                              labels[:labels.shape[0] // 2])
        feed.append((tokens, labels))
    params = jax.device_put(make_weights(), one)
    return ref.follow(params, feed, model=ctx.config["model"],
                      optimizer=ctx.config["optimizer"],
                      state_dtypes=ctx.config["state_dtypes"],
                      cast_name=cast_name)


def run(ctx) -> harness.Result:
    import jax
    from apex_tpu.analysis import hot_path_guard
    from apex_tpu.transformer import parallel_state

    fs, make_weights, ref = build(ctx)
    params = make_weights()
    step_guess = 0.1                       # s; only sizes the batch list
    batches = make_batches(
        ctx, fs, CHECKED_STEPS + min(512, math.ceil(ctx.seconds / step_guess)))
    params, opt_state, got = first_steps(
        ctx, fs, params, make_weights, batches, ref)
    feed = batches[CHECKED_STEPS:]
    tokens_per_step = feed[0][0].size
    tracer = harness.Tracer(ctx.trace, ctx.seconds)

    # -- the window ---------------------------------------------------------
    losses = []
    with hot_path_guard("train window", transfers=None,
                        tripwire=False) as guard:
        t0 = now = time.perf_counter()
        while True:
            tracer.start_if_due(now - t0)
            tokens, labels = feed[len(losses) % len(feed)]
            with tracer.span("train_step"):
                params, opt_state, loss = fs.step(
                    params, opt_state, tokens, labels)
                losses.append(float(loss))
            now = time.perf_counter()
            if now - t0 >= ctx.seconds:
                break
        window_s = now - t0
        tracer.stop()
        recompiles = guard.recompiles
    steps = len(losses)
    result = harness.Result(
        attempted=steps,
        failed=sum(1 for x in losses if not math.isfinite(x)),
        end_to_end={"train_tokens_per_s": steps * tokens_per_step / window_s},
        window_start=t0, window_s=window_s,
        memory_peak_bytes=harness.memory_peak_bytes(fs.mesh.devices.flat),
        checks=[],
        counters={"steps": steps, "tokens_per_step": tokens_per_step,
                  "seq": ctx.mix["seq"], "chips": fs.mesh.size,
                  "batch": feed[0][0].shape[0],
                  "recompiles_in_window": recompiles})
    if ctx.trace:
        result.trace, result.trace_window_ns, result.trace_window_s = \
            tracer.reduce()

    # -- the comparison, once the program's state is freed -------------------
    batches = batches[:CHECKED_STEPS]
    del params, opt_state, feed, fs
    parallel_state.destroy_model_parallel()
    harness.free_device_memory()
    want = reference_readings(ctx, ref, make_weights, batches)
    result.checks = compare(got, want, ctx.limits)
    result.checks.append(harness.Check(
        "recompiles_in_window", float(recompiles), 0.0))
    return result
