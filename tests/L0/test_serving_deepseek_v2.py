"""The third architecture behind ``ServingEngine`` (ISSUE 33): a
``deepseek_v2`` decoder (multi-head latent attention over a one-operand
latent page, expanded at prefill and absorbed over the pages; a
group-limited softmax router over the dropless expert layer; prefix
sharing) against its plain reference, at CPU size with every ratio kept
(a rotary part, two ranks, 8 groups of which 3 are kept), on the XLA
routes and the kernel in interpret mode."""

import math
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

from reference import deepseek_v2_serve as ref  # noqa: E402

from apex_tpu.ops import attention  # noqa: E402
from apex_tpu.ops.attention import routing_override  # noqa: E402
from apex_tpu.serving import (DeepseekV2Config, ServingEngine,  # noqa: E402
                              SimClock, SpecConfig)
from apex_tpu.serving.experts import expert_layer, route_grouped  # noqa: E402
from apex_tpu.serving.kv_cache import PagedKVCache, PrefixIndex  # noqa: E402
from apex_tpu.serving.model import (PagedDecoder, yarn_inv_freq,  # noqa: E402
                                    yarn_mscale)
from apex_tpu.telemetry import PHASE_RING  # noqa: E402

PS = 8
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 32,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
MODEL = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=64,
    moe_intermediate_size=24, n_routed_experts=2, router_width=16,
    experts_held=[0, 2], num_experts_per_tok=3, n_group=8, topk_group=3,
    routed_scaling_factor=16, n_shared_experts=2, rope_theta=10000,
    rope_scaling=YARN, rms_norm_eps=1e-6)
SHAPE = ref.model_shape(MODEL)
logits_all = jax.jit(ref.logits_all, static_argnames="shape")


def config(held=(0, 2), dtype=jnp.float32) -> DeepseekV2Config:
    return DeepseekV2Config(
        vocab_size=96, hidden_size=32, num_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, num_layers=3, first_k_dense_replace=1,
        intermediate_size=64, moe_intermediate_size=24, n_routed_experts=16,
        experts_held=held, top_k=3, n_group=8, topk_group=3,
        routed_scaling_factor=16.0, n_shared_experts=2,
        rope_original_max=32, dtype=dtype)


CFG = config()


@pytest.fixture(scope="module")
def params():
    return CFG.init_params(5)


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
    logits = np.asarray(logits_all(params, jnp.asarray(seq, jnp.int32),
                                   shape=SHAPE))
    rows = logits[len(req.prompt) - 1:len(seq) - 1]
    return float(np.max(rows.max(-1)
                        - rows[np.arange(len(rows)), req.generated]))


def latent_cache(dec, n_pages=40):
    return PagedKVCache(
        num_layers=dec.full_layers, num_pages=n_pages, page_size=PS,
        num_heads=CFG.kv_heads, head_dim=CFG.head_dim,
        max_pages_per_request=16, latent_dim=CFG.latent_dim)


def front(a, width):
    a = np.asarray(a, np.int32)
    return jnp.asarray(np.pad(a, (width - len(a), 0))[None])


# -- (a) the decoder over the latent pool against the reference ---------------

@pytest.mark.parametrize("route", ["xla", "decode"])
def test_prefill_then_decode_agrees_with_the_reference_everywhere(
        params, route):
    dec = PagedDecoder(CFG)
    seq = prompts(1, (37,))[0]
    want = np.asarray(logits_all(params, jnp.asarray(seq, jnp.int32),
                                 shape=SHAPE))
    C, S = 11, 16
    cache = latent_cache(dec)
    assert cache.operands == ("k",) and cache.v is None
    assert cache.k.shape[-1] == 128        # 24 numbers, one lane tile
    row = lambda a: jnp.asarray(np.pad(np.asarray(a, np.int32),
                                       (0, S - C))[None])
    with routing_override(decode=route):
        logits, latent, stats = dec.prefill(
            params, row(seq[:C]), row(np.ones(C)), row(np.arange(C)))
        assert latent.shape == (3, 1, S, CFG.latent_dim)
        np.testing.assert_allclose(np.asarray(logits[0, :C]), want[:C],
                                   atol=2e-4)
        pages = cache.allocate(cache.pages_needed(len(seq)), 0)
        idx = np.arange(C)
        pad = lambda a: np.pad(a, (0, S - C))
        cache.write_tokens(latent[:, 0], None,
                           pad(np.asarray(pages)[idx // PS]), pad(idx % PS))
        decode = jax.jit(dec.decode)
        table = cache.page_table([pages])
        for p in range(C, len(seq)):
            out = decode(params, cache.k, None,
                         jnp.asarray([seq[p]], jnp.int32),
                         jnp.asarray([p], jnp.int32), table,
                         jnp.asarray([p + 1], jnp.int32))
            assert len(out) == 3           # logits, ONE pool, the counters
            cache.k = out[1]
            np.testing.assert_allclose(np.asarray(out[0][0]), want[p],
                                       atol=2e-4, err_msg=f"position {p}")
    # what the padding lanes hold never changed
    assert not np.asarray(cache.k[..., CFG.latent_dim:]).any()


@pytest.mark.parametrize("route", ["xla", "decode"])
def test_a_prompt_chunked_over_three_chunks_agrees_with_the_reference(
        params, route):
    dec = PagedDecoder(CFG)
    chunk = 16
    seq = prompts(2, (2 * chunk + 7,))[0]
    want = np.asarray(logits_all(params, jnp.asarray(seq, jnp.int32),
                                 shape=SHAPE))
    cache = latent_cache(dec)
    pages = cache.allocate(cache.pages_needed(len(seq)), 0)
    table = cache.page_table([pages])
    with routing_override(decode=route):
        extend = jax.jit(dec.extend)
        for start in range(0, len(seq), chunk):
            n = min(chunk, len(seq) - start)
            pos = np.arange(start, start + n)
            out = extend(
                params, cache.k, None, front(seq[start:start + n], chunk),
                front(pos, chunk),
                front(np.asarray(pages)[pos // PS], chunk),
                front(pos % PS, chunk), table,
                jnp.asarray([start + n], jnp.int32))
            cache.k = out[1]
            np.testing.assert_allclose(
                np.asarray(out[0][0, chunk - n:]), want[start:start + n],
                atol=2e-4, err_msg=f"chunk at {start}")


def test_engine_serves_what_the_reference_puts_first(params):
    eng = engine(params)
    eng.warmup()
    reqs = [eng.submit(p, n) for p, n in zip(prompts(), (6, 9, 12, 8, 20))]
    eng.run()
    for req in reqs:
        assert req.finish_reason == "length"
        assert reference_gap(params, req) < 1e-4
    assert eng.cache.pages_used == 0


# -- (b) absorbed equals expanded on the same latent pages --------------------

def test_absorbed_over_the_pages_equals_expanded_over_the_row(params):
    """The same tokens, the whole row expanded (no cache) and the last
    eight absorbed over the pages the first ones filled: one
    mathematics, two orders of multiplication."""
    dec = PagedDecoder(CFG)
    seq = prompts(3, (24,))[0]
    row = lambda a: jnp.asarray(np.asarray(a, np.int32)[None])
    logits, latent, _ = dec.prefill(params, row(seq), row(np.ones(24)),
                                    row(np.arange(24)))
    cache = latent_cache(dec)
    pages = cache.allocate(3, 0)
    idx = np.arange(16)
    cache.write_tokens(latent[:, 0, :16], None,
                       np.asarray(pages)[idx // PS], idx % PS)
    pos = np.arange(16, 24)
    out = dec.extend(params, cache.k, None, row(seq[16:]), row(pos),
                     row(np.asarray(pages)[pos // PS]), row(pos % PS),
                     cache.page_table([pages]), jnp.asarray([24], jnp.int32))
    np.testing.assert_allclose(np.asarray(out[0][0]),
                               np.asarray(logits[0, 16:]), atol=2e-4)
    # ... and the vectors the chunk appended are the row's own
    np.testing.assert_allclose(
        np.asarray(out[1][:, pages[2], :, :CFG.latent_dim]),
        np.asarray(latent[:, 0, 16:]), atol=1e-5)


# -- (c) the shares add up ----------------------------------------------------

def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """The routed parts that all 8 groups' shares give, with the shared
    experts counted once, are the uncut reference layer; a token whose
    kept groups leave a share out gets exactly zero from it."""
    d, f, E, T = 32, 24, 16, 40
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 16))
    mat = lambda *s: jax.random.normal(next(keys), s) / math.sqrt(s[-2])
    whole = {"router": mat(d, E),
             "experts": {"wg": mat(E, d, f), "wu": mat(E, d, f),
                         "wd": mat(E, f, d)},
             "shared": {"wg": mat(d, 2 * f), "wu": mat(d, 2 * f),
                        "wd": mat(2 * f, d)}}
    u = jax.random.normal(next(keys), (T, d))
    kw = dict(top_k=3, route_scale=16.0, groups=(8, 3))
    shared = expert_layer(u, {**whole, "experts": jax.tree_util.tree_map(
        lambda a: a[:0], whole["experts"])}, held=(0, 0), **kw)[0]
    experts, _ = route_grouped(u, whole["router"], top_k=3, route_scale=16.0,
                               n_group=8, topk_group=3)
    total = shared
    for g in range(8):
        held = (2 * g, 2 * g + 2)
        share = {**whole, "experts": jax.tree_util.tree_map(
            lambda a: a[held[0]:held[1]], whole["experts"])}
        y, load = expert_layer(u, share, held=held, **kw)
        routed = y - shared
        total = total + routed
        out = ~np.any((np.asarray(experts) >= held[0])
                      & (np.asarray(experts) < held[1]), axis=-1)
        assert out.any()
        assert not np.asarray(routed)[out].any()
        assert int(load.sum()) == int((~out).sum()
                                      + np.sum(np.sum(
                                          (np.asarray(experts) >= held[0])
                                          & (np.asarray(experts) < held[1]),
                                          -1) == 2))
    # the uncut layer, by the reference's own equations
    uncut = SHAPE._replace(held=(0, 16), router_width=16)
    with jax.default_matmul_precision("highest"):
        score = jax.nn.softmax(u @ whole["router"], axis=-1)
        sel, w, _ = ref.route(score, uncut)
        swiglu = lambda p: (jax.nn.silu(u @ p["wg"]) * (u @ p["wu"])) @ p["wd"]
        want = swiglu(whole["shared"])
        for e in range(E):
            w_e = jnp.sum(jnp.where(sel == e, w, 0.0), -1)
            want = want + w_e[:, None] * swiglu(jax.tree_util.tree_map(
                lambda a: a[e], whole["experts"]))
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1),
                                  np.sort(np.asarray(experts), -1))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-4)


def test_the_router_keeps_three_groups_and_does_not_renormalise():
    rng = np.random.RandomState(4)
    u = jnp.asarray(rng.randn(50, 32), jnp.float32)
    router = jnp.asarray(rng.randn(32, 16) / 4, jnp.float32)
    experts, w = route_grouped(u, router, top_k=3, route_scale=16.0,
                               n_group=8, topk_group=3)
    score = np.asarray(jax.nn.softmax(u @ router, -1))
    for t in range(50):
        groups = np.argsort(-score[t].reshape(8, 2).max(-1))[:3]
        allowed = np.where(np.isin(np.arange(16) // 2, groups), score[t], 0)
        want = np.argsort(-allowed)[:3]
        assert sorted(experts[t].tolist()) == sorted(want.tolist())
        np.testing.assert_allclose(
            np.sort(np.asarray(w[t])), np.sort(score[t][want] * 16.0),
            rtol=1e-5)
    assert not np.allclose(np.asarray(w).sum(-1), 16.0)


# -- (e) YaRN by hand-computed values -----------------------------------------

def test_yarn_frequencies_and_scale_at_the_published_sizes():
    inv, low, high = yarn_inv_freq(64, theta=10000.0, factor=40.0,
                                   original_max=4096, beta_fast=32.0,
                                   beta_slow=1.0)
    assert (low, high) == (10, 23)
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv[:11], f[:11])            # untouched
    np.testing.assert_allclose(inv[23:], f[23:] / 40.0)     # all of factor
    i = 16                                                  # ramp 6/13
    np.testing.assert_allclose(
        inv[i], f[i] / 40 * (6 / 13) + f[i] * (7 / 13))
    assert abs(yarn_mscale(40.0, 0.707) - 1.2608) < 1e-4
    published = DeepseekV2Config(
        vocab_size=8, hidden_size=8, num_heads=128, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_layers=1, first_k_dense_replace=1,
        intermediate_size=8, moe_intermediate_size=8, n_routed_experts=160,
        experts_held=(0, 20), top_k=6, n_group=8, topk_group=3,
        routed_scaling_factor=16.0, n_shared_experts=2)
    assert abs(published.softmax_scale - 0.114721) < 1e-6
    assert published.latent_dim == 576 and published.head_dim == 256
    # the reference computes the same, from the file's own keys
    full = ref.model_shape({**MODEL, "qk_rope_head_dim": 64,
                            "qk_nope_head_dim": 128, "rope_scaling": {
                                **YARN,
                                "original_max_position_embeddings": 4096}})
    r_inv, r_low, r_high = ref.yarn_frequencies(full)
    assert (r_low, r_high) == (10, 23)
    np.testing.assert_allclose(r_inv, inv)
    assert abs(ref.softmax_scale(full) - 0.114721) < 1e-6


# -- (f) prefix sharing -------------------------------------------------------

def test_a_second_request_on_a_registered_document_computes_only_its_suffix(
        params):
    eng = engine(params, prefix_sharing=True, prefix_entries=8)
    eng.warmup()
    doc = prompts(6, (40,))[0]                 # five whole pages
    first = eng.submit(doc + [3, 1, 4], 4)
    eng.run()
    PHASE_RING.clear()
    second = eng.submit(doc + [2, 7, 1, 8, 2, 8], 7)
    eng.run()
    assert second.prefix_hit and second.prefix_tokens == 40
    spans = [r for r in PHASE_RING.snapshot() if r.name == "engine.prefill"]
    assert [(s.attrs["C"], s.attrs["shared"], s.attrs["ctx"])
            for s in spans] == [(6, 40, 46)]
    steps = [r for r in PHASE_RING.snapshot() if r.name == "engine.step"]
    assert steps[0].attrs["prefix_entries"] == 1
    assert steps[0].attrs["prefix_pages_shared"] == 5
    for req in (first, second):
        assert reference_gap(params, req) < 1e-4


def test_a_match_that_ends_inside_a_page_copies_the_page_first(params):
    eng = engine(params, prefix_sharing=True)
    eng.warmup()
    doc = prompts(7, (40,))[0]
    eng.submit(doc, 2)
    eng.run()
    [entry] = eng.prefix_index.entries
    before = np.asarray(eng.cache.k[:, eng.prefix_index._entries[entry][0]])
    # parts from the document inside its fifth page
    branch = eng.submit(doc[:35] + [9, 9, 9, 9, 9, 9, 9], 5)
    eng.run()
    assert branch.prefix_tokens == 35
    assert reference_gap(params, branch) < 1e-4
    # bit for bit what the shared pages held before the second reader
    after = np.asarray(eng.cache.k[:, eng.prefix_index._entries[entry][0]])
    np.testing.assert_array_equal(before, after)


def lookup_by_tokens(index, tokens):
    """The lookup as it walked until ISSUE 33, a token at a time."""
    ctx = tuple(int(t) for t in tokens)
    ps = index.cache.page_size
    best_m, best_pages = 0, []
    for key, (pages, _) in index._entries.items():
        lim = min(len(key), len(ctx) - 1)
        m = 0
        while m < lim and key[m] == ctx[m]:
            m += 1
        if m >= ps and m > best_m:
            best_m, best_pages = m, pages[:index.cache.pages_needed(m)]
    return best_m, list(best_pages)


def test_the_lookup_by_pages_finds_what_the_walk_by_tokens_found():
    cache = PagedKVCache(num_layers=1, num_pages=256, page_size=PS,
                         num_heads=1, head_dim=8, max_pages_per_request=16)
    index = PrefixIndex(cache, max_entries=12)
    rng = np.random.RandomState(8)
    docs = [list(map(int, rng.randint(0, 5, 40))) for _ in range(4)]
    for i in range(12):
        key = docs[i % 4][:int(rng.randint(8, 41))] \
            + list(map(int, rng.randint(0, 5, int(rng.randint(0, 30)))))
        index.register(key, cache.allocate(cache.pages_needed(len(key)), i))
    hits = 0
    for _ in range(200):
        doc = docs[int(rng.randint(4))]
        ctx = doc[:int(rng.randint(1, 41))] \
            + list(map(int, rng.randint(0, 5, int(rng.randint(0, 20)))))
        got = index.lookup(ctx)
        assert got == lookup_by_tokens(index, ctx)
        assert got[0] <= len(ctx) - 1
        hits += bool(got[0])
    assert hits > 100


def test_with_72_entries_every_document_still_hits_after_200_registrations():
    """The mix's own draw: 8 documents, each context a document and a
    question of its own; every finished prefill registers its context
    and the oldest entry goes.  With 8 entries a document is lost
    whenever none of the last eight contexts began with it."""
    import traffic

    ps, doc_len = 8, 64
    mix = {"loop": "backlog", "block": 32, "schedule_seed": 33,
           "shared_prefix": {"count": 8, "length": doc_len},
           "prompt_len": {"dist": "uniform", "lo": doc_len + 8,
                          "hi": doc_len + 24},
           "max_new": {"dist": "uniform", "lo": 1, "hi": 2}}

    def misses(entries):
        cache = PagedKVCache(num_layers=1, num_pages=4096, page_size=ps,
                             num_heads=1, head_dim=8,
                             max_pages_per_request=16)
        index = PrefixIndex(cache, max_entries=entries)
        source = traffic.requests(mix, 12345, 96)
        docs, missed = set(), 0
        for i in range(200 + 8):
            ctx = next(source).prompt
            m, _ = index.lookup(ctx)
            if tuple(ctx[:doc_len]) in docs:
                missed += m < doc_len
            docs.add(tuple(ctx[:doc_len]))
            aligned = len(ctx) // ps * ps
            index.register(ctx[:aligned],
                           cache.allocate(aligned // ps, i))
        assert len(docs) == 8
        return missed

    assert misses(72) == 0
    assert misses(8) > 20


# -- (g) what it refuses; batching is invisible -------------------------------

@pytest.mark.parametrize("option", [
    dict(tp=2), dict(kv_quant="int8"),
    dict(spec=SpecConfig(k=2)), dict(prefill_only=True),
    dict(kv_import=True)])
def test_an_option_deepseek_v2_does_not_carry_raises_at_construction(
        params, option):
    with pytest.raises(ValueError, match="deepseek_v2"):
        engine(params, **option)


def test_a_latent_page_is_not_shipped(params):
    eng = engine(params)
    with pytest.raises(ValueError, match="latent"):
        eng.cache.export_page_bytes(1)


def test_batched_decoding_equals_sequential_decoding(params):
    batched = engine(params)
    reqs = [batched.submit(p, 10) for p in prompts(9, (6, 21, 13))]
    batched.run()
    for req in reqs:
        alone = engine(params)
        one = alone.submit(req.prompt, 10)
        alone.run()
        assert one.generated == req.generated


def test_sharing_pages_changes_no_token(params):
    doc = prompts(10, (32,))[0]
    asks = [doc + q for q in prompts(11, (3, 9, 5))]
    shared = engine(params, prefix_sharing=True)
    plain = engine(params)
    got = []
    for eng in (shared, plain):
        eng.submit(doc, 1)
        eng.run()
        reqs = [eng.submit(a, 8) for a in asks]
        eng.run()
        got.append([r.generated for r in reqs])
    assert all(r.prefix_hit for r in shared.sched.finished[1:])
    assert got[0] == got[1]


def test_rows_on_one_document_walk_it_together_and_serve_the_same(
        params, monkeypatch):
    """ISSUE 36, through the engine and the interpreted kernel: with
    sharing on, three questions on one document decode as one tile
    whose shared blocks are fetched once; the streams are those of an
    engine in which every request holds pages of its own, where every
    block walked is a block fetched."""
    # a block of two pages, so that the toy document is two whole blocks
    monkeypatch.setattr(attention, "_LATENT_BLOCK_COLS", 2 * PS)
    doc = prompts(10, (32,))[0]
    asks = [doc + q for q in prompts(11, (3, 9, 5))]
    got, walks = [], []
    with routing_override(decode="decode"):
        for sharing in (True, False):
            eng = engine(params, prefix_sharing=sharing)
            eng.submit(doc, 1)
            eng.run()
            PHASE_RING.clear()
            reqs = [eng.submit(a, 8) for a in asks]
            eng.run()
            got.append([r.generated for r in reqs])
            walks.append([
                (r.attrs["latent_blocks_walked"],
                 r.attrs["latent_blocks_fetched"])
                for r in PHASE_RING.snapshot()
                if r.name == "engine.decode" and r.attrs["rids"]])
    assert got[0] == got[1]
    together, apart = walks
    assert together and all(w >= f > 0 for w, f in together)
    # three rows, two shared blocks: four fetches fewer a step
    assert max(w - f for w, f in together) == 4
    assert apart and all(w == f > 0 for w, f in apart)


def test_recover_and_defrag_carry_the_one_operand(params):
    eng = engine(params)
    a = eng.submit(prompts(12, (20,))[0], 3)
    eng.step()
    eng.cache.defrag([r.pages for r in eng.sched.running])
    eng.recover("device_loss")
    assert eng.cache.v is None and eng.cache.operands == ("k",)
    eng.run()
    assert reference_gap(params, a) < 1e-4


def test_the_ring_and_the_executables_name_what_the_model_adds(params):
    eng = engine(params)
    PHASE_RING.clear()
    eng.submit(prompts(13, (12,))[0], 3)
    eng.run()
    decodes = [r for r in PHASE_RING.snapshot() if r.name == "engine.decode"]
    # ... on the span in which the launch's tokens landed (ISSUE 34)
    assert decodes and all(
        {"moe_pairs_held", "moe_load_max", "latent_blocks_walked",
         "latent_blocks_fetched"} <= set(r.attrs)
        for r in decodes if r.attrs["rids"])
    assert sum(bool(r.attrs["rids"]) for r in decodes) == eng.decode_steps
    lowered = eng.analysis_executables()
    text = lowered["decode"].as_text(debug_info=True)
    for scope in ("mla_q", "mla_kv_down", "mla_absorb", "attn_latent",
                  "moe_router", "moe_experts", "moe_shared"):
        assert scope in text, scope
    assert "mla_expand" in lowered["prefill"].as_text(debug_info=True)
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    fresh = engine(params)      # a trace is cached with the route it took
    for name in ("decode", "chunk"):
        structs = fresh._executable_arg_structs()[name]
        with routing_override(decode="decode"):
            walk(jax.make_jaxpr(fresh._exec_defs[name][0])(*structs).jaxpr)
    assert names == ["flash_decode_latent"] * 6     # three layers, twice
