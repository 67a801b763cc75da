"""The fifth architecture behind ``ServingEngine`` (ISSUE 39): an
``lfm2_moe`` decoder (LiquidAI LFM2-MoE: gated short-convolution layers
beside QK-normed grouped-query layers, two dense layers, then a top-k
sigmoid expert layer without a shared expert, a tied head) whose
requests own a SLOT that holds a convolution tail alone beside their
pages, against its plain reference, at CPU size with both kinds of
layer in the expert layers (d = 64, 4 query heads over 2 K/V heads of
16, 8 experts top-2, taps 3), float32, on the XLA routes and the paged
kernel in interpret mode.

Tolerances: program and reference are both float32; they differ by the
order of reductions (the paged kernel's online softmax, the grouped
products, XLA's fusions), about 1e-6 of logits of order 1.  2e-4 leaves
two orders of magnitude above that and lies two below what one bfloat16
rounding of the activations moves a logit by (about 1e-2)."""

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

from reference import lfm2_moe_serve as ref  # noqa: E402

from apex_tpu.ops import causal_conv, short_conv_update  # noqa: E402
from apex_tpu.ops.attention import routing_override  # noqa: E402
from apex_tpu.serving import (Lfm2MoeConfig, ServingEngine,  # noqa: E402
                              SimClock, SpecConfig)
from apex_tpu.serving.experts import expert_layer, swiglu  # noqa: E402
from apex_tpu.serving.kv_cache import (PagedKVCache,  # noqa: E402
                                       PagePoolExhausted, StatePool)
from apex_tpu.serving.model import PagedDecoder, StateIO  # noqa: E402
from apex_tpu.telemetry import PHASE_RING  # noqa: E402

PS = 8
TOL = 2e-4
TYPES = ("conv", "conv", "full_attention", "conv", "conv", "full_attention")
MODEL = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, layer_types=list(TYPES), num_hidden_layers=6,
    num_dense_layers=2, intermediate_size=128, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, routed_scaling_factor=1.0,
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
    use_expert_bias=True, rope_parameters={"rope_theta": 1000000,
                                           "rope_type": "default"})
SHAPE = ref.model_shape(MODEL)

#: LFM2-24B-A2B's row of the model-configs catalog (``config``), as
#: published at https://huggingface.co/LiquidAI/LFM2-24B-A2B
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv"] + ["full_attention", "conv", "conv", "conv"] * 8
    + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def config(**kw) -> Lfm2MoeConfig:
    base = dict(
        vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
        layer_types=TYPES, num_dense_layers=2, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, experts_held=(0, 8),
        top_k=2)
    return Lfm2MoeConfig(**{**base, **kw})


CFG = config()


@pytest.fixture(scope="module")
def params():
    return CFG.init_params(5)


def logits_all(params, seq):
    return np.asarray(ref.logits_all(params, jnp.asarray(seq, jnp.int32),
                                     SHAPE))


def engine(params, **kw):
    kw = {"num_pages": 64, "page_size": PS, "max_batch": 3,
          "max_pages_per_request": 16, "prefill_budget": 16,
          "clock": SimClock(), **kw}
    return ServingEngine(CFG, params, **kw)


def prompts(seed=0, lens=(5, 14, 40, 23, 33)):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(0, 96, n))) for n in lens]


def reference_gap(params, req) -> float:
    """How far the reference puts a served token below its own best."""
    seq = req.prompt + req.generated
    rows = logits_all(params, seq)[len(req.prompt) - 1:len(seq) - 1]
    return float(np.max(rows.max(-1)
                        - rows[np.arange(len(rows)), req.generated]))


def caches(dec, n_pages=40, n_slots=4):
    cache = PagedKVCache(
        num_layers=dec.full_layers, num_pages=n_pages, page_size=PS,
        num_heads=CFG.kv_heads, head_dim=dec.page_head_dim,
        max_pages_per_request=16)
    cache.state_pool = StatePool(
        num_layers=dec.n_state_layers, num_slots=n_slots,
        tail_shape=CFG.tail_shape)
    return cache, cache.state_pool


def front(a, width):
    a = np.asarray(a, np.int32)
    return jnp.asarray(np.pad(a, (width - len(a), 0))[None])


def io(spool, slot, fresh=False):
    return StateIO(None, spool.conv, jnp.asarray([slot], jnp.int32),
                   jnp.asarray([int(fresh)], jnp.int32))


# -- (a) the decoder over pages and tail-only slots against the reference -----

def test_the_families_of_layers_and_the_tail_only_slot():
    dec = PagedDecoder(CFG)
    assert (dec.full_layers, dec.window_layers, dec.n_state_layers) \
        == (2, 0, 4)
    assert dec.pool_index == (0, 1, 0, 2, 3, 1)
    assert dec.state_planes == ("conv",) and dec.kv_stacks == 3
    assert (CFG.head_dim, dec.page_head_dim) == (16, 128)
    assert CFG.tail_shape == (2, 64) and CFG.state_shape is None
    full = config(hidden_size=2048, num_heads=32, num_kv_heads=8)
    assert (full.head_dim, full.page_head_dim, full.tail_shape) \
        == (64, 128, (2, 2048))


@pytest.mark.parametrize("route", ["xla", "decode"])
def test_prefill_then_decode_agrees_with_the_reference_everywhere(
        params, route):
    dec = PagedDecoder(CFG)
    seq = prompts(1, (37,))[0]
    want = logits_all(params, seq)
    C, S, slot = 11, 16, 2
    cache, spool = caches(dec)
    row = lambda a: jnp.asarray(np.pad(np.asarray(a, np.int32),
                                       (0, S - C))[None])
    with routing_override(decode=route):
        logits, k, v, tail, stats = dec.prefill(
            params, row(seq[:C]), row(np.ones(C)), row(np.arange(C)))
        assert k.shape == (2, 1, S, 2, 128)
        assert tail.shape == (4, 1) + CFG.tail_shape
        assert stats.shape == (4,)       # pairs, max, rows tiled, touched
        np.testing.assert_allclose(np.asarray(logits[0, :C]), want[:C],
                                   atol=TOL)
        pages = cache.allocate(cache.pages_needed(len(seq)), 0)
        idx = np.arange(C)
        pad = lambda a: np.pad(a, (0, S - C))
        cache.write_tokens(k[:, 0], v[:, 0],
                           pad(np.asarray(pages)[idx // PS]), pad(idx % PS))
        spool.write(slot, tail[:, 0])
        decode = jax.jit(dec.decode)
        table = cache.page_table([pages])
        for p in range(C, len(seq)):
            out = decode(params, cache.k, cache.v,
                         jnp.asarray([seq[p]], jnp.int32),
                         jnp.asarray([p], jnp.int32), table,
                         jnp.asarray([p + 1], jnp.int32),
                         state=io(spool, slot))
            assert len(out) == 5       # logits, K, V, tails, counters
            cache.k, cache.v, spool.conv = out[1:4]
            np.testing.assert_allclose(np.asarray(out[0][0]), want[p],
                                       atol=TOL, err_msg=f"position {p}")
    # the slots nobody named kept their zeros
    assert not np.asarray(spool.conv[:, [1, 3]]).any()


@pytest.mark.parametrize("route", ["xla", "decode"])
def test_a_prompt_chunked_over_three_chunks_agrees_with_the_reference(
        params, route):
    """Three front-padded chunks, the last of 7 tokens: each starts from
    the tail the one before left in the slot (the convolution's window
    crosses each chunk boundary), the first from zero whatever the slot
    held."""
    dec = PagedDecoder(CFG)
    chunk, slot = 16, 1
    seq = prompts(2, (2 * chunk + 7,))[0]
    want = logits_all(params, seq)
    cache, spool = caches(dec)
    spool.conv = spool.conv - 2.0          # the last owner's leavings
    pages = cache.allocate(cache.pages_needed(len(seq)), 0)
    table = cache.page_table([pages])
    with routing_override(decode=route):
        extend = jax.jit(dec.extend)
        for start in range(0, len(seq), chunk):
            n = min(chunk, len(seq) - start)
            pos = np.arange(start, start + n)
            out = extend(
                params, cache.k, cache.v, front(seq[start:start + n], chunk),
                front(pos, chunk),
                front(np.asarray(pages)[pos // PS], chunk),
                front(pos % PS, chunk), table,
                jnp.asarray([start + n], jnp.int32),
                state=io(spool, slot, fresh=start == 0))
            cache.k, cache.v, spool.conv = out[1:4]
            np.testing.assert_allclose(
                np.asarray(out[0][0, chunk - n:]), want[start:start + n],
                atol=TOL, err_msg=f"chunk at {start}")
    # ... and a whole row over the same tokens leaves the same tail
    S = 48
    row = lambda a: jnp.asarray(np.pad(np.asarray(a, np.int32),
                                       (0, S - len(seq)))[None])
    tail = dec.prefill(params, row(seq), row(np.ones(len(seq))),
                       row(np.arange(len(seq))))[3]
    np.testing.assert_allclose(np.asarray(spool.conv[:, slot]),
                               np.asarray(tail[:, 0]), atol=1e-5)


def test_the_short_convolutions_two_forms_by_hand():
    """A row through ``causal_conv`` (no bias, no activation) from a
    tail, and the same tokens one by one through ``short_conv_update``:
    ``c_t = w0 v_{t-2} + w1 v_{t-1} + w2 v_t``, the tail the last two
    ``v``."""
    rng = np.random.RandomState(3)
    v = rng.randn(5, 4).astype(np.float32)
    w = rng.randn(3, 4).astype(np.float32)
    before = rng.randn(2, 4).astype(np.float32)
    seq = np.concatenate([before, v])
    want = np.stack([w[0] * seq[t] + w[1] * seq[t + 1] + w[2] * seq[t + 2]
                     for t in range(5)])
    c, tail = causal_conv(jnp.asarray(v), jnp.asarray(before),
                          jnp.ones((5,), bool), jnp.asarray(w), None, None)
    np.testing.assert_allclose(np.asarray(c), want, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail), v[-2:])
    pool = jnp.zeros((2, 3, 2, 4), jnp.float32).at[1, 2].set(before)
    for t in range(5):
        got, pool = short_conv_update(pool, jnp.asarray([2, 0], jnp.int32),
                                      jnp.asarray(np.stack([v[t], v[t]])),
                                      layer=1, conv_w=jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got[0]), want[t], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(pool[1, 2]), v[-2:])
    assert not np.asarray(pool[0]).any() and not np.asarray(pool[1, 1]).any()
    with pytest.raises(ValueError, match="layer"):
        short_conv_update(pool, jnp.asarray([1]), jnp.asarray(v[:1]),
                          layer=2, conv_w=jnp.asarray(w))


def test_engine_serves_what_the_reference_puts_first(params):
    eng = engine(params)
    eng.warmup()
    reqs = [eng.submit(p, n) for p, n in zip(prompts(), (6, 9, 12, 8, 20))]
    eng.run()
    for req in reqs:
        assert req.finish_reason == "length"
        assert reference_gap(params, req) < 1e-4
    assert eng.cache.pages_used == 0
    assert eng.cache.state_pool.slots_used == 0


def test_batched_decoding_equals_sequential_decoding(params):
    batched = engine(params)
    reqs = [batched.submit(p, 10) for p in prompts(9, (6, 21, 13))]
    batched.run()
    for req in reqs:
        alone = engine(params)
        one = alone.submit(req.prompt, 10)
        alone.run()
        assert one.generated == req.generated


def test_preemption_frees_the_slot_and_is_output_invisible(params):
    free = engine(params)
    want = [free.submit(p, 24) for p in prompts(5, (10, 12))]
    free.run()
    eng = engine(params, num_pages=8, max_pages_per_request=7)
    reqs = [eng.submit(p, 24) for p in prompts(5, (10, 12))]
    eng.run()
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.generated for r in reqs] == [r.generated for r in want]
    assert eng.cache.state_pool.slots_used == 0


# -- (b) the expert layer without a shared expert ------------------------------

def test_the_expert_layer_without_a_shared_expert_is_a_loop_over_experts(
        params):
    """Every expert held, no shared expert, ``w / (sum w + 1e-6)``: the
    layer against a plain loop over the chosen experts."""
    moe = params["layers"][3]["moe"]
    assert "shared" not in moe
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64), jnp.float32)
    got, load = expert_layer(u, moe, held=(0, 8), top_k=2, route_scale=1.0,
                             route_eps=1e-6)
    flat = np.asarray(u).reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(flat @ moe["router"]))
    want, pairs = np.zeros_like(flat), np.zeros(8, np.int64)
    for t in range(flat.shape[0]):
        sel = np.argsort(-(s[t] + np.asarray(moe["expert_bias"])))[:2]
        w = s[t, sel] / (s[t, sel].sum() + 1e-6)
        for e, we in zip(sel, w):
            p = jax.tree_util.tree_map(lambda a: a[e], moe["experts"])
            want[t] += we * np.asarray(swiglu(jnp.asarray(flat[t]), p))
            pairs[e] += 1
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 64), want,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(load), pairs)
    # the epsilon is the keyword's: afmoe's 1e-20 gives other weights
    other, _ = expert_layer(u, moe, held=(0, 8), top_k=2, route_scale=1.0)
    assert not np.array_equal(np.asarray(other), np.asarray(got))


# -- (c) a slot that holds a tail alone ---------------------------------------

def test_a_tail_only_slot_pool_hands_out_slots_as_granites_does():
    pool = StatePool(num_layers=2, num_slots=4, tail_shape=(2, 64),
                     dtype=jnp.bfloat16)
    assert pool.ssm is None and pool.planes == ("conv",)
    assert pool.conv.shape == (2, 4, 2, 64) and pool.conv.dtype == jnp.bfloat16
    assert pool.arrays == (pool.conv,) and pool.slots_free == 3
    a, b, c = (pool.allocate(owner) for owner in (7, 8, 9))
    assert (a, b, c) == (1, 2, 3) and pool.owner_of(2) == 8
    with pytest.raises(PagePoolExhausted, match="slot"):
        pool.allocate(10)
    pool.free(b)
    assert pool.allocate(11) == 2
    for bad in (0, 7):
        with pytest.raises(ValueError, match="scratch|double"):
            pool.free(bad)
    np.testing.assert_array_equal(pool.table([3, 1], rows=4), [3, 1, 0, 0])
    pool.write(3, jnp.ones((2, 2, 64)))
    assert np.asarray(pool.conv[:, 3], np.float32).all()
    assert not np.asarray(pool.conv[:, :3], np.float32).any()
    with pytest.raises(ValueError, match="scratch"):
        StatePool(num_layers=1, num_slots=1, tail_shape=(2, 64))
    # granite's pool holds both planes, in that order
    both = StatePool(num_layers=1, num_slots=2, tail_shape=(1, 8),
                     state_shape=(8, 128))
    assert both.planes == ("ssm", "conv")


def test_a_retired_requests_slot_is_reused_and_the_newcomer_starts_from_zero(
        params):
    eng = engine(params, state_slots=3)
    a, b, c = (eng.submit(p, n) for p, n in zip(
        prompts(4, (9, 12, 35)), (3, 14, 6)))
    taken = {}
    while not eng.sched.idle:
        eng.step()
        for req in (a, b, c):
            if req.slot is not None:
                taken.setdefault(req.rid, req.slot)
        assert eng.cache.state_pool.slots_used <= 2
    assert taken[c.rid] == taken[a.rid]
    for req in (a, b, c):
        assert reference_gap(params, req) < 1e-4


# -- (d) what it refuses ------------------------------------------------------

@pytest.mark.parametrize("option", [
    dict(tp=2), dict(kv_quant="int8"), dict(prefix_sharing=True),
    dict(spec=SpecConfig(k=2)), dict(prefill_only=True),
    dict(kv_import=True)])
def test_an_option_lfm2_does_not_carry_raises_at_construction(
        params, option):
    with pytest.raises(ValueError, match="lfm2_moe.*What LFM2 refuses"):
        engine(params, **option)


def test_a_slot_is_not_shipped(params):
    eng = engine(params)
    req = eng.submit(prompts(13, (6,))[0], 4)
    eng.step()
    with pytest.raises(ValueError, match="slots of a state pool"):
        eng.export_request(req.rid)


# -- (e) the reference ---------------------------------------------------------

def test_the_reference_reads_the_published_keys():
    """The catalog row's own keys give the published model's sizes, and
    what the reference does not implement is refused."""
    m = ref.model_shape(PUBLISHED)
    assert (m.hidden, m.heads, m.kv_heads, m.head_dim) == (2048, 32, 8, 64)
    assert (m.ffn, m.expert_ffn, m.router_width, m.held, m.top_k) \
        == (11776, 1536, 64, (0, 64), 4)
    assert (m.taps, m.theta, m.eps, m.route_scale) == (3, 1e6, 1e-5, 1.0)
    assert m.layer_types.count("conv") == 30
    assert [i for i, t in enumerate(m.layer_types)
            if t == "full_attention"] == list(range(2, 40, 4))
    layout = ref.param_layout({**PUBLISHED, "num_hidden_layers": 10,
                               "layer_types": PUBLISHED["layer_types"][:10]})
    assert layout["layers"][0]["w_in"][0] == (2048, 6144)
    assert layout["layers"][0]["conv_w"][0] == (3, 2048)
    assert layout["layers"][2]["wk"][0] == (2048, 512)
    assert layout["layers"][9]["moe"]["experts"]["wg"][0] == (64, 2048, 1536)
    assert "head" not in layout and "shared" not in layout["layers"][9]["moe"]
    for bad in (dict(conv_bias=True), dict(use_expert_bias=False),
                dict(tie_word_embeddings=False), dict(num_hidden_layers=39),
                dict(layer_types=["sliding_attention"] * 40)):
        with pytest.raises(ValueError):
            ref.model_shape({**PUBLISHED, **bad})


def test_the_configuration_file_is_the_catalog_row_cut_in_depth():
    import json

    with open(os.path.join(BENCH, "configs",
                           "lfm2-24b-a2b-serve-l10.json")) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, want in PUBLISHED.items():
        if key not in config["reduced"]:
            assert config[key] == want, key
    assert config["layer_types"] == PUBLISHED["layer_types"][:10]
    assert config["num_hidden_layers"] == 10
    assert config["published"]["num_hidden_layers"] == 40
    assert config["model"]["experts_held"] == [0, 64]
    assert config["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"


def test_the_tail_reset_control_moves_the_logits_where_decoding_begins():
    import weights

    params = weights.make(ref.param_layout(MODEL), 4, jnp.float32)
    tokens = jnp.asarray(prompts(8, (24,))[0], jnp.int32)
    exact, _ = ref.hidden_states(params, tokens, SHAPE)
    reset, _ = ref.hidden_states(params, tokens, SHAPE, "tail_reset",
                                 reset_at=12)
    gap = np.abs(np.asarray(reset) - np.asarray(exact)).max(-1)
    assert gap[:12].max() == 0.0
    assert gap[12] > 1e-2 and gap[13] > 1e-2


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "lfm2_moe_serve.py")) as f:
        assert "apex_tpu" not in f.read()


# -- (f) the ring and the executables ------------------------------------------

def test_the_ring_and_the_executables_name_what_the_model_adds(params):
    eng = engine(params)
    PHASE_RING.clear()
    eng.submit(prompts(14, (12,))[0], 3)
    eng.submit(prompts(14, (40,))[0], 3)
    eng.run()
    ring = PHASE_RING.snapshot()
    # a landed launch's counters: 4 expert layers, 2 pairs a row each
    decodes = [r.attrs for r in ring if r.name == "engine.decode"
               and "moe_pairs_held" in r.attrs]
    assert decodes
    for d in decodes:
        assert d["moe_pairs_held"] % 8 == 0
        assert 1 <= d["moe_experts_touched"] <= min(4 * 8,
                                                    d["moe_pairs_held"])
        assert d["moe_load_max"] <= d["moe_pairs_held"] // 4
    prefills = [r.attrs for r in ring if r.name == "engine.prefill"]
    # a whole row, then three chunks: the first from zero
    assert [p["state_in"] for p in prefills] == [0, 0, 1, 1]
    lowered = eng.analysis_executables()
    text = lowered["decode"].as_text(debug_info=True)
    for scope in ("conv_in_proj", "short_conv", "conv_out_proj",
                  "attn_full", "mlp", "moe_router", "moe_experts"):
        assert scope in text, scope
    assert "moe_shared" not in text
