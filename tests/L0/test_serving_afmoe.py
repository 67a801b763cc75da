"""The second architecture behind ``ServingEngine`` (ISSUE 29): an
``afmoe`` decoder (sliding-window and full grouped-query layers over two
page lifetimes, a dropless top-k expert layer that holds a share of the
experts) against its plain reference, at CPU size, on the XLA routes and
the kernels in interpret mode."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import afmoe_serve as ref  # noqa: E402

from apex_tpu.analysis import hot_path_guard  # noqa: E402
from apex_tpu.ops.attention import routing_override  # noqa: E402
from apex_tpu.serving import (ServingEngine, ServingModelConfig,  # noqa: E402
                              SimClock, SpecConfig)
from apex_tpu.serving.experts import expert_layer  # noqa: E402
from apex_tpu.serving.kv_cache import (PagedKVCache, PagePoolExhausted,  # noqa: E402
                                       WindowPages, WindowPool)
from apex_tpu.serving.model import (AfmoeConfig, PagedDecoder,  # noqa: E402
                                    WindowKV)
from apex_tpu.telemetry import PHASE_RING  # noqa: E402

W = 20            # the sliding window
PS = 8            # page size (the kernel's grain at float32)
MODEL = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=6,
    num_key_value_heads=2, head_dim=8,
    layer_types=["sliding_attention", "sliding_attention",
                 "full_attention", "sliding_attention"],
    num_dense_layers=1, intermediate_size=64, moe_intermediate_size=24,
    num_experts=4, router_width=16, experts_held=[4, 8],
    num_experts_per_tok=3, route_scale=2.448, sliding_window=W,
    rope_theta=10000, rms_norm_eps=1e-5)
SHAPE = ref.model_shape(MODEL)
logits_all = jax.jit(ref.logits_all, static_argnames="shape")


def config(held=(4, 8), dtype=jnp.float32) -> AfmoeConfig:
    return AfmoeConfig(
        vocab_size=96, hidden_size=32, num_heads=6, num_kv_heads=2,
        head_dim=8, layer_types=tuple(MODEL["layer_types"]),
        num_dense_layers=1, intermediate_size=64, moe_intermediate_size=24,
        num_experts=16, experts_held=held, top_k=3, route_scale=2.448,
        sliding_window=W, dtype=dtype)


CFG = config()


@pytest.fixture(scope="module")
def params():
    return CFG.init_params(3)


def engine(params, **kw):
    kw = {"num_pages": 40, "window_pages": 24, "page_size": PS,
          "max_batch": 3, "max_pages_per_request": 16,
          "prefill_budget": 16, "clock": SimClock(), **kw}
    return ServingEngine(CFG, params, **kw)


def prompts(seed=0, lens=(5, 14, 40, 23, 33)):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(0, 96, n))) for n in lens]


def reference_gap(params, req) -> float:
    """How far the reference puts a served token below its own best."""
    seq = req.prompt + req.generated
    logits = np.asarray(logits_all(params, jnp.asarray(seq, jnp.int32),
                                   shape=SHAPE))
    rows = logits[len(req.prompt) - 1:len(seq) - 1]
    return float(np.max(rows.max(-1)
                        - rows[np.arange(len(rows)), req.generated]))


# -- (a) the decoder over the two-pool cache against the reference ------------

class TwoPools:
    """The engine's use of the cache, by hand: the full layers' pool and
    the window layers', one request."""

    def __init__(self, dec, n_pages=40, chunk=8):
        geo = dict(page_size=PS, num_heads=CFG.kv_heads,
                   head_dim=CFG.head_dim, num_pages=n_pages)
        self.full = PagedKVCache(num_layers=dec.full_layers,
                                 max_pages_per_request=16, **geo)
        self.win = WindowPool(
            window=W, num_layers=dec.window_layers,
            max_pages_per_request=WindowPool.pages_per_request(W, chunk, PS),
            **geo)
        self.pages, self.held = [], WindowPages()

    def take(self, first_query, end):
        need = self.full.pages_needed(end) - len(self.pages)
        self.pages += self.full.allocate(max(need, 0), 0)
        self.win.grow(self.held, first_query, end, 0)

    def tables(self):
        wpages, wstart = self.win.tables([self.held])
        return (self.full.page_table([self.pages]),
                WindowKV(self.win.k, self.win.v, wpages, wstart))

    def bind(self, out):
        self.full.k, self.full.v, self.win.k, self.win.v = out[:4]


@pytest.mark.parametrize("route", ["xla", "decode"])
def test_prefill_then_decode_agrees_with_the_reference_everywhere(
        params, route):
    dec = PagedDecoder(CFG)
    seq = prompts(1, (44,))[0]
    want = np.asarray(logits_all(params, jnp.asarray(seq, jnp.int32),
                                 shape=SHAPE))
    C, S = 9, 16
    pools = TwoPools(dec)
    row = lambda a: jnp.asarray(np.pad(np.asarray(a, np.int32),
                                       (0, S - C))[None])
    with routing_override(decode=route):
        logits, k, v, wk, wv, stats = dec.prefill(
            params, row(seq[:C]), row(np.ones(C)), row(np.arange(C)))
        np.testing.assert_allclose(np.asarray(logits[0, :C]), want[:C],
                                   atol=2e-4)
        pools.take(C, C)
        idx = np.arange(C)
        pad = lambda a: np.pad(a, (0, S - C))
        pools.full.write_tokens(k[:, 0], v[:, 0], pad(np.asarray(
            pools.pages)[idx // PS]), pad(idx % PS))
        wp, wo = pools.win.write_targets(pools.held, idx)
        pools.win.write_tokens(wk[:, 0], wv[:, 0], pad(wp), pad(wo))
        released = 0
        decode = jax.jit(dec.decode)
        for p in range(C, len(seq)):
            pools.take(p, p + 1)
            table, window = pools.tables()
            out = decode(
                params, pools.full.k, pools.full.v,
                jnp.asarray([seq[p]], jnp.int32),
                jnp.asarray([p], jnp.int32), table,
                jnp.asarray([p + 1], jnp.int32), window=window)
            pools.bind(out[1:])
            np.testing.assert_allclose(np.asarray(out[0][0]), want[p],
                                       atol=2e-4, err_msg=f"position {p}")
            released += pools.win.slide(pools.held, p + 1)
    assert released == (len(seq) - W + 1) // PS
    assert len(pools.pages) == pools.full.pages_needed(len(seq))


@pytest.mark.parametrize("route", ["xla", "decode"])
def test_chunked_prefill_beyond_window_and_chunk_agrees_with_the_reference(
        params, route):
    dec = PagedDecoder(CFG)
    chunk = 8
    seq = prompts(2, (W + chunk + 13,))[0]
    want = np.asarray(logits_all(params, jnp.asarray(seq, jnp.int32),
                                 shape=SHAPE))
    pools = TwoPools(dec, chunk=chunk)
    with routing_override(decode=route):
        extend = jax.jit(dec.extend)
        for start in range(0, len(seq), chunk):
            n = min(chunk, len(seq) - start)
            pools.take(start, start + n)
            pos = np.arange(start, start + n)
            front = lambda a: jnp.asarray(np.pad(
                np.asarray(a, np.int32), (chunk - n, 0))[None])
            table, window = pools.tables()
            out = extend(
                params, pools.full.k, pools.full.v,
                front(seq[start:start + n]), front(pos),
                front(np.asarray(pools.pages)[pos // PS]), front(pos % PS),
                table, jnp.asarray([start + n], jnp.int32), window=window)
            pools.bind(out[1:])
            np.testing.assert_allclose(
                np.asarray(out[0][0, chunk - n:]), want[start:start + n],
                atol=2e-4, err_msg=f"chunk at {start}")
            pools.win.slide(pools.held, start + n)
            assert len(pools.held.pages) <= pools.win.max_pages_per_request
    # only the window's tail is still held there; the full pool kept all
    assert pools.held.base == pools.win.first_slot(len(seq))
    assert len(pools.pages) == pools.full.pages_needed(len(seq))


def test_engine_serves_what_the_reference_puts_first(params):
    eng = engine(params)
    eng.warmup()
    reqs = [eng.submit(p, n) for p, n in zip(prompts(), (6, 9, 12, 8, 20))]
    eng.run()
    for req in reqs:
        assert req.finish_reason == "length"
        assert reference_gap(params, req) < 1e-4
    assert eng.cache.pages_used == 0
    assert eng.cache.window_pool.pages_used == 0


def test_a_ladder_of_prefill_rows_fills_both_pools_as_the_one_row_did(params):
    # ISSUE 32: a row as wide as the prompt needs (a 256 row or its half),
    # scattered into the full pool and the window's tail at that width;
    # longer prompts still go by chunks of the widest
    PHASE_RING.clear()
    eng = engine(params, prefill_budget=256, num_pages=200, window_pages=60,
                 max_pages_per_request=48)
    assert eng.prefill_widths == (128, 256)
    assert eng.chunk_size == 256
    eng.warmup()
    lens = (9, 128, 150, 250, 300)
    with hot_path_guard("afmoe, every rung", transfers=None) as g:
        reqs = [eng.submit(p, 6) for p in prompts(11, lens)]
        eng.run()
    assert g.recompiles == 0
    for req in reqs:
        assert req.finish_reason == "length"
        assert reference_gap(params, req) < 1e-4
    rows = {}
    for r in PHASE_RING.snapshot():
        if r.name == "engine.prefill":
            rows.setdefault(r.attrs["rid"], []).append(
                (r.attrs["C"], r.attrs["S"]))
    assert [rows[req.rid] for req in reqs] == [
        [(9, 128)], [(128, 128)], [(150, 256)], [(250, 256)],
        [(256, 256), (44, 256)]]          # the last by chunks
    assert eng.cache.pages_used == 0
    assert eng.cache.window_pool.pages_used == 0


# -- (b) the shares add up ----------------------------------------------------

def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of 16 experts, and the shared
    expert once, are the reference's layer with every expert held."""
    whole = config(held=(0, 16))
    moe = whole.init_params(5)["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 32), jnp.float32)
    kw = dict(top_k=3, route_scale=2.448)
    full, load = expert_layer(u, moe, held=(0, 16), **kw)
    assert int(load.sum()) == 2 * 7 * 3              # no token dropped
    shared_only = {**moe, "experts": jax.tree_util.tree_map(
        lambda a: a[:0], moe["experts"])}
    shared, _ = expert_layer(u, shared_only, held=(0, 0), **kw)
    total = shared
    for lo in range(0, 16, 4):
        share = {**moe, "experts": jax.tree_util.tree_map(
            lambda a: a[lo:lo + 4], moe["experts"])}
        part, load = expert_layer(u, share, held=(lo, lo + 4), **kw)
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               atol=1e-5)
    # and that is the reference's expert layer, computed its own way
    model = {**MODEL, "num_experts": 16, "experts_held": [0, 16]}
    shape = ref.model_shape(model)
    x = u.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        score = jax.nn.sigmoid(x @ moe["router"])
        _, sel = jax.lax.top_k(score + moe["expert_bias"], shape.top_k)
        w = jnp.take_along_axis(score, sel, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * shape.route_scale
        mlp = lambda p, e=None: (
            jax.nn.silu(x @ (p["wg"] if e is None else p["wg"][e]))
            * (x @ (p["wu"] if e is None else p["wu"][e]))) \
            @ (p["wd"] if e is None else p["wd"][e])
        want = mlp(moe["shared"])
        for e in range(16):
            want = want + jnp.where(sel == e, w, 0).sum(-1)[:, None] \
                * mlp(moe["experts"], e)
    np.testing.assert_allclose(np.asarray(full.reshape(-1, 32)),
                               np.asarray(want), atol=1e-4)


def test_padding_takes_no_expert():
    moe = CFG.init_params(5)["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(2), (6, 32), jnp.float32)
    valid = jnp.asarray([True, True, False, True, False, False])
    _, every = expert_layer(u, moe, held=(4, 8), top_k=3, route_scale=1.0)
    y, load = expert_layer(u, moe, held=(4, 8), top_k=3, route_scale=1.0,
                           valid=valid)
    _, real = expert_layer(u[np.asarray(valid)], moe, held=(4, 8), top_k=3,
                           route_scale=1.0)
    assert load.tolist() == real.tolist() and load.sum() <= every.sum()
    assert np.isfinite(np.asarray(y)).all()


# -- (d) the allocator --------------------------------------------------------

def live_window_pages(eng):
    return [p for r in eng.sched.running if r.window is not None
            for p in r.window.pages]


def test_window_pages_go_back_as_the_window_slides_and_full_pages_do_not(
        params):
    eng = engine(params)
    wpool = eng.cache.window_pool
    req = eng.submit(prompts()[0], 60)          # 5 + 60 tokens, window 20
    held_full, most = [], 0
    while not eng.sched.idle:
        eng.step()
        if req in eng.sched.running:
            held_full.append(len(req.pages))
            most = max(most, len(req.window.pages))
            # no page of the live window is ever on a free list
            assert not set(req.window.pages) & set(wpool._free)
            assert req.window.base == wpool.first_slot(req.kv_len)
            assert not set(req.pages) & set(eng.cache._free)
    assert held_full == sorted(held_full)       # the full pool only grows
    assert held_full[-1] == eng.cache.pages_needed(64)
    assert most <= WindowPool.pages_per_request(W, 1, PS)
    assert wpool.pages_used == 0 and eng.cache.pages_used == 0
    released = sum(r.attrs["released_window"]
                   for r in PHASE_RING.snapshot()
                   if r.name == "engine.release")
    assert released > 0


def test_both_pools_are_empty_after_every_request_retires(params):
    eng = engine(params)
    for p, n in zip(prompts(3, (7, 30, 41, 12, 25, 36)), (9, 5, 14, 3, 8, 6)):
        eng.submit(p, n)
    while not eng.sched.idle:
        eng.step()
        assert len(set(live_window_pages(eng))) == len(live_window_pages(eng))
        assert not set(live_window_pages(eng)) & set(
            eng.cache.window_pool._free)
    assert eng.cache.pages_used == 0
    assert eng.cache.window_pool.pages_used == 0
    assert all(r.window is None for r in eng.sched.finished)


def test_preemption_and_reprefill_of_a_long_request_is_output_invisible(
        params):
    """A window pool too small for everyone: a long request is evicted
    mid-stream, re-prefilled in chunks, and serves the same tokens."""
    def streams(**kw):
        eng = engine(params, **kw)
        reqs = [eng.submit(p, n) for p, n in
                zip(prompts(4, (38, 9, 30, 11)), (16, 18, 14, 12))]
        eng.run()
        assert eng.cache.window_pool.pages_used == 0
        assert eng.cache.pages_used == 0
        return reqs

    roomy = streams()
    tight = streams(window_pages=9)
    assert sum(r.preemptions for r in tight) > 0
    assert sum(r.preemptions for r in roomy) == 0
    assert [r.generated for r in tight] == [r.generated for r in roomy]


def test_a_dry_window_pool_with_nothing_to_preempt_raises(params):
    eng = engine(params, window_pages=2)   # one allocatable page
    eng.submit(prompts()[1], 4)            # a 14-token row wants two
    with pytest.raises(PagePoolExhausted):
        eng.step()


def test_defrag_moves_each_pools_own_pages(params):
    eng = engine(params)
    reqs = [eng.submit(p, 30) for p in prompts(5, (20, 6, 27))]
    for _ in range(12):
        eng.step()
    running = list(eng.sched.running)
    before = [list(r.generated) for r in reqs]
    eng.cache.defrag([r.pages for r in running])
    eng.cache.window_pool.defrag([r.window.pages for r in running])
    held = sorted(p for r in running for p in r.window.pages)
    assert held == list(range(1, len(held) + 1))
    eng.run()
    control = engine(params)
    again = [control.submit(list(r.prompt), 30) for r in reqs]
    control.run()
    assert [r.generated for r in reqs] == [r.generated for r in again]
    assert all(r.generated[:len(b)] == b for r, b in zip(reqs, before))


# -- (e) what afmoe refuses ---------------------------------------------------

@pytest.mark.parametrize("option", [
    dict(tp=2), dict(kv_quant="int8"),
    dict(prefix_sharing=True, spec=SpecConfig(k=0, chunk_size=8)),
    dict(spec=SpecConfig(k=2)), dict(prefill_only=True),
    dict(kv_import=True)], ids=lambda o: "+".join(o))
def test_an_option_afmoe_does_not_carry_raises_at_construction(
        params, option):
    with pytest.raises(ValueError, match="afmoe.*not supported"):
        engine(params, **option)


@pytest.mark.parametrize("call", ["export_request", "adopt_prefilled"])
def test_shipping_window_pages_raises(params, call):
    eng = engine(params)
    req = eng.submit(prompts()[0], 4)
    eng.step()
    args = (req.rid,) if call == "export_request" else ({}, [], 0)
    with pytest.raises(ValueError, match="afmoe.*window pool"):
        getattr(eng, call)(*args)


def test_a_model_without_a_position_table_is_bounded_by_its_pages(params):
    eng = engine(params)
    assert CFG.max_position is None
    assert eng.max_context == 16 * PS == eng.sched.max_position
    assert eng.chunk_size == eng.prefill_budget      # chunked by default
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit([1] * (16 * PS - 4), 5)
    eng.submit([1] * (16 * PS - 5), 5)                # fills its pages
    with pytest.raises(ValueError, match="prefill_budget"):
        ServingEngine(CFG, params, num_pages=8, page_size=PS)
    gpt = ServingEngine(ServingModelConfig(max_position=48), num_pages=20,
                        page_size=8)
    assert gpt.max_context == 48 and gpt.cache.window_pool is None
    assert gpt.chunk_size is None


# -- (f) batched equals sequential --------------------------------------------

def test_batched_decoding_equals_sequential_decoding(params):
    work = list(zip(prompts(6, (5, 14, 40, 23, 33, 8)), (6, 9, 12, 8, 20, 7)))
    batched = engine(params)
    together = [batched.submit(p, n) for p, n in work]
    batched.run()
    alone = []
    for p, n in work:
        eng = engine(params)
        alone.append(eng.submit(p, n))
        eng.run()
    assert [r.generated for r in together] == [r.generated for r in alone]


def test_snapshot_and_restore_rebuild_both_pools(params):
    eng = engine(params)
    reqs = [eng.submit(p, n) for p, n in zip(prompts(7, (26, 7)), (12, 15))]
    for _ in range(6):
        eng.step()
    snap = eng.snapshot()
    fresh = engine(params)
    restored = fresh.restore(snap)
    fresh.run()
    eng.run()
    assert [r.generated for r in restored] == [r.generated for r in reqs]


def test_recover_rebuilds_the_window_pool(params):
    eng = engine(params)
    reqs = [eng.submit(p, n) for p, n in zip(prompts(8, (21, 35)), (10, 10))]
    for _ in range(5):
        eng.step()
    old = eng.cache.window_pool
    eng.recover("device_loss")
    assert eng.cache.window_pool is not old
    assert eng.cache.window_pool.num_pages == old.num_pages
    assert eng.sched.wpool is eng.cache.window_pool
    eng.run()
    control = engine(params)
    again = [control.submit(list(r.prompt), r.max_new_tokens) for r in reqs]
    control.run()
    assert [r.generated for r in reqs] == [r.generated for r in again]


# -- spans, counters and names ------------------------------------------------

def test_the_ring_holds_the_page_and_expert_counters(params):
    PHASE_RING.clear()
    eng = engine(params)
    eng.submit(prompts()[2], 10)        # 40 tokens: chunked
    eng.submit(prompts()[0], 10)        # 5 tokens: one row
    eng.run()
    by_name = {}
    for r in PHASE_RING.snapshot():
        by_name.setdefault(r.name, []).append(r.attrs or {})
    steps = [a for a in by_name["engine.step"] if "held_full" in a]
    assert steps and all(
        {"held_window", "held_uniform", "released_full"} <= set(a)
        for a in steps)
    assert any(a["held_window"] < a["held_uniform"] for a in steps)
    assert sum(a["released_full"] for a in steps) == \
        eng.cache.pages_needed(50) + eng.cache.pages_needed(15)
    assert all("released_window" in a for a in by_name["engine.release"])
    # the counters ride the deferred fetch with the tokens (ISSUE 34):
    # a span carries those of the launch whose tokens LANDED in it
    landings = [a for a in by_name["engine.decode"] if a["rids"]]
    assert len(landings) == eng.decode_steps        # every launch lands
    for a in landings:
        assert 0 < a["moe_load_max"] <= a["moe_pairs_held"] <= \
            len(a["rids"]) * 3 * 3
    assert all("moe_load_max" not in a for a in by_name["engine.decode"]
               if not a["rids"])
    done = [a for a in by_name["engine.prefill"] if "moe_pairs_held" in a]
    assert len(done) == 2               # the row, and the last chunk
    assert len(by_name["engine.prefill"]) == 1 + 3


def test_gpt_steps_carry_none_of_it():
    PHASE_RING.clear()
    eng = ServingEngine(ServingModelConfig(max_position=48), num_pages=20,
                        page_size=8, clock=SimClock())
    eng.submit([1, 2, 3], 4)
    eng.run()
    names = {r.name for r in PHASE_RING.snapshot()}
    assert "engine.release" not in names
    assert not any("held_full" in (r.attrs or {})
                   for r in PHASE_RING.snapshot())


def test_the_executables_name_their_layers_and_kernels(params):
    eng = engine(params)
    with routing_override(decode="decode"):
        text = eng.analysis_executables()["decode"].as_text(
            debug_info=True)
    for scope in ("moe_router", "moe_experts", "moe_shared", "attn_window",
                  "attn_full"):
        assert scope in text, scope
    dec = PagedDecoder(CFG)
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    structs = eng._executable_arg_structs()["decode"]
    with routing_override(decode="decode"):
        walk(jax.make_jaxpr(eng._exec_defs["decode"][0])(*structs).jaxpr)
    # the three expert layers' grouped products, gate and up in one call
    grouped = [n for n in names if n.startswith("grouped_swiglu")]
    assert grouped == ["grouped_swiglu_gate_up", "grouped_swiglu_down"] * 3
    assert [n for n in names if n not in grouped] == [
        "flash_decode_window", "flash_decode_window", "flash_decode",
        "flash_decode_window"]
    assert dec.pool_index == (0, 1, 0, 2)
