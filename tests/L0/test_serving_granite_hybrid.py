"""The fourth architecture behind ``ServingEngine`` (ISSUE 35): a
``granitemoehybrid`` decoder without experts (Granite 4.0-H: nine
Mamba-2 state-space layers to one NoPE grouped-query layer, the Granite
multipliers, a tied head) whose requests own a SLOT of recurrent state
beside their pages, against its plain reference, at CPU size with every
ratio kept (9 : 1 layers in a period, 4 query heads a K/V head, one B/C
group, a convolution of 4), on the XLA routes and the kernels in
interpret mode."""

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

from reference import granite_hybrid_serve as ref  # noqa: E402

from apex_tpu.ops.attention import routing_override  # noqa: E402
from apex_tpu.serving import (GraniteHybridConfig, ServingEngine,  # noqa: E402
                              SimClock, SpecConfig)
from apex_tpu.serving.kv_cache import (PagedKVCache,  # noqa: E402
                                       PagePoolExhausted, StatePool)
from apex_tpu.serving.model import PagedDecoder, StateIO  # noqa: E402
from apex_tpu.serving.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler, Request)
from apex_tpu.telemetry import PHASE_RING  # noqa: E402

PS = 8
TYPES = ["attention" if i == 5 else "mamba" for i in range(10)]
MODEL = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, layer_types=TYPES, shared_intermediate_size=128,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8, rms_norm_eps=1e-5,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.125, logits_scaling=8, num_local_experts=0,
    tie_word_embeddings=True, position_embedding_type="nope")
SHAPE = ref.model_shape(MODEL)


def config(**kw) -> GraniteHybridConfig:
    base = dict(
        vocab_size=96, hidden_size=64, num_heads=8, num_kv_heads=2,
        layer_types=tuple(TYPES), intermediate_size=128, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
        mamba_chunk_size=8, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.125,
        logits_scaling=8.0, rms_norm_eps=1e-5)
    return GraniteHybridConfig(**{**base, **kw})


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
        state_shape=CFG.state_shape, tail_shape=CFG.tail_shape)
    return cache, cache.state_pool


def front(a, width):
    a = np.asarray(a, np.int32)
    return jnp.asarray(np.pad(a, (width - len(a), 0))[None])


def io(spool, slot, fresh=False):
    return StateIO(spool.ssm, spool.conv, jnp.asarray([slot], jnp.int32),
                   jnp.asarray([int(fresh)], jnp.int32))


# -- (a) the decoder over pages and slots against the reference ---------------

def test_the_families_of_layers_and_the_padded_pool():
    dec = PagedDecoder(CFG)
    assert (dec.full_layers, dec.window_layers, dec.n_state_layers) \
        == (1, 0, 9)
    assert dec.pool_index == (0, 1, 2, 3, 4, 0, 5, 6, 7, 8)
    assert dec.kv_stacks == 4
    # a head of 8 is stored in a whole lane tile, zeros after; at the
    # published sizes a head of 64 likewise
    assert (CFG.head_dim, dec.page_head_dim) == (8, 128)
    full = config(hidden_size=2048, num_heads=32, num_kv_heads=8)
    assert (full.head_dim, full.page_head_dim) == (64, 128)
    assert PagedDecoder(config(hidden_size=1024)).page_head_dim == 128


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
        logits, k, v, state, tail = dec.prefill(
            params, row(seq[:C]), row(np.ones(C)), row(np.arange(C)))
        assert k.shape == (1, 1, S, 2, 128)
        assert not np.asarray(k[..., 8:]).any()
        assert state.shape == (9, 1) + CFG.state_shape
        assert tail.shape == (9, 1) + CFG.tail_shape
        np.testing.assert_allclose(np.asarray(logits[0, :C]), want[:C],
                                   atol=2e-4)
        pages = cache.allocate(cache.pages_needed(len(seq)), 0)
        idx = np.arange(C)
        pad = lambda a: np.pad(a, (0, S - C))
        cache.write_tokens(k[:, 0], v[:, 0],
                           pad(np.asarray(pages)[idx // PS]), pad(idx % PS))
        spool.write(slot, state[:, 0], tail[:, 0])
        decode = jax.jit(dec.decode)
        table = cache.page_table([pages])
        for p in range(C, len(seq)):
            out = decode(params, cache.k, cache.v,
                         jnp.asarray([seq[p]], jnp.int32),
                         jnp.asarray([p], jnp.int32), table,
                         jnp.asarray([p + 1], jnp.int32),
                         state=io(spool, slot))
            assert len(out) == 5       # logits, K, V, states, tails
            cache.k, cache.v, spool.ssm, spool.conv = out[1:]
            np.testing.assert_allclose(np.asarray(out[0][0]), want[p],
                                       atol=2e-4, err_msg=f"position {p}")
    # the slots nobody named kept their zeros
    assert not np.asarray(spool.ssm[:, [1, 3]]).any()


@pytest.mark.parametrize("route", ["xla", "decode"])
def test_a_prompt_chunked_over_three_chunks_agrees_with_the_reference(
        params, route):
    """Three front-padded chunks, the last of 7 tokens: each starts from
    the state and tail the one before left in the slot, the first from
    zero whatever the slot held."""
    dec = PagedDecoder(CFG)
    chunk, slot = 16, 1
    seq = prompts(2, (2 * chunk + 7,))[0]
    want = logits_all(params, seq)
    cache, spool = caches(dec)
    spool.ssm = spool.ssm + 3.0            # the last owner's leavings
    spool.conv = spool.conv - 2.0
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
            cache.k, cache.v, spool.ssm, spool.conv = out[1:]
            np.testing.assert_allclose(
                np.asarray(out[0][0, chunk - n:]), want[start:start + n],
                atol=2e-4, err_msg=f"chunk at {start}")
    # ... and a whole row over the same tokens leaves the same state
    S = 48
    row = lambda a: jnp.asarray(np.pad(np.asarray(a, np.int32),
                                       (0, S - len(seq)))[None])
    _, _, _, state, tail = dec.prefill(
        params, row(seq), row(np.ones(len(seq))), row(np.arange(len(seq))))
    np.testing.assert_allclose(np.asarray(spool.ssm[:, slot]),
                               np.asarray(state[:, 0]), atol=2e-4)
    np.testing.assert_allclose(np.asarray(spool.conv[:, slot]),
                               np.asarray(tail[:, 0]), atol=1e-5)


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


# -- (d) slots ----------------------------------------------------------------

def test_the_slot_pool_hands_out_lowest_first_and_never_the_scratch_slot():
    pool = StatePool(num_layers=2, num_slots=4, state_shape=(16, 128),
                     tail_shape=(3, 160))
    assert pool.ssm.dtype == jnp.float32 and pool.slots_free == 3
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
    with pytest.raises(ValueError, match="scratch"):
        StatePool(num_layers=1, num_slots=1, state_shape=(16, 128),
                  tail_shape=(3, 160))


def test_a_retired_requests_slot_is_reused_and_the_newcomer_starts_from_zero(
        params):
    """Two slots besides the scratch one, three requests, the longest
    of them chunked: the third takes the slot the first gave back, and
    serves what it serves alone."""
    eng = engine(params, state_slots=3)
    a, b, c = (eng.submit(p, n) for p, n in zip(
        prompts(4, (9, 12, 35)), (3, 14, 6)))
    taken = {}
    for _ in range(200):
        if eng.sched.idle:
            break
        eng.step()
        for req in (a, b, c):
            if req.slot is not None:
                taken.setdefault(req.rid, req.slot)
        # the third waits for a slot, pages or not
        assert eng.cache.state_pool.slots_used <= 2
    assert taken[c.rid] == taken[a.rid]
    for req in (a, b, c):
        alone = engine(params)
        one = alone.submit(req.prompt, req.max_new_tokens)
        alone.run()
        assert one.generated == req.generated
        assert reference_gap(params, req) < 1e-4


def test_a_request_with_pages_but_no_slot_is_not_admitted():
    dec = PagedDecoder(CFG)
    cache, spool = caches(dec, n_slots=3)
    sched = ContinuousBatchingScheduler(
        cache, max_batch=4, prefill_budget=64, max_position=128)
    for rid in range(3):
        sched.submit(Request(rid=rid, prompt=[1] * 5, max_new_tokens=4))
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [0, 1]
    assert sorted(r.slot for r in admitted) == [1, 2]
    assert cache.pages_free > 10 and cache.slots_free == 0
    assert sched.waiting[0].slot is None and not sched.waiting[0].pages
    # a retirement gives the slot back with the pages
    admitted[0].generated = [1, 2, 3, 4]
    sched.retire_finished(0.0)
    assert cache.slots_free == 1 and admitted[0].slot is None
    assert [r.rid for r in sched.admit()] == [2]


def test_preemption_frees_the_slot_and_is_output_invisible(params):
    """A pool too small for both requests' growth: the newer is evicted
    (slot and pages back), re-prefills into a fresh slot and serves
    what it would have."""
    free = engine(params)
    want = [free.submit(p, 24) for p in prompts(5, (10, 12))]
    free.run()
    eng = engine(params, num_pages=8, max_pages_per_request=7)
    reqs = [eng.submit(p, 24) for p in prompts(5, (10, 12))]
    held = []
    for _ in range(300):
        if eng.sched.idle:
            break
        eng.step()
        held.append(eng.cache.state_pool.slots_used)
        for req in eng.sched.waiting:
            assert req.slot is None
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.generated for r in reqs] == [r.generated for r in want]
    assert eng.cache.state_pool.slots_used == 0 and max(held) == 2


def test_recover_leaves_no_slot_held(params):
    eng = engine(params)
    reqs = [eng.submit(p, 6) for p in prompts(6, (7, 20))]
    for _ in range(3):
        eng.step()
    assert eng.cache.state_pool.slots_used == 2
    eng.recover("device_loss")
    assert eng.cache.state_pool.slots_used == 0
    assert all(r.slot is None for r in reqs)
    eng.run()
    for req in reqs:
        assert reference_gap(params, req) < 1e-4
    assert eng.cache.state_pool.slots_used == 0


def test_snapshot_and_restore_re_prefill_from_tokens(params):
    eng = engine(params)
    req = eng.submit(prompts(7, (18,))[0], 9)
    for _ in range(4):
        eng.step()
    snap = eng.snapshot()
    eng.run()
    other = engine(params)
    [again] = other.restore(snap)
    other.run()
    assert again.generated == req.generated


# -- (e) batching and the launch in flight are invisible ----------------------

def test_batched_decoding_equals_sequential_decoding(params):
    batched = engine(params)
    reqs = [batched.submit(p, 10) for p in prompts(9, (6, 21, 13))]
    batched.run()
    for req in reqs:
        alone = engine(params)
        one = alone.submit(req.prompt, 10)
        alone.run()
        assert one.generated == req.generated


def test_one_launch_in_flight_serves_what_landing_every_step_serves(params):
    """A state advances once a committed token: with a launch in flight
    (launch n + 1 reads the state launch n wrote on the device) the
    tokens are those of an engine that lands every launch before the
    next, an EOS overrun (a launch after an EOS that had not landed)
    included."""
    free = engine(params)
    probe = free.submit(prompts(10, (9,))[0], 12)
    free.run()
    eos = probe.generated[5]
    runs = []
    for land in (False, True):
        eng = engine(params)
        reqs = [eng.submit(p, 12, eos_id=eos)
                for p in prompts(10, (9, 17, 4))]
        PHASE_RING.clear()
        while not eng.sched.idle:
            eng.step()
            if land:
                eng._land()
        eng.run()
        launched = sum(r.attrs["rows"] for r in PHASE_RING.snapshot()
                       if r.name == "engine.decode")
        landed = sum(len(r.attrs["rids"]) for r in PHASE_RING.snapshot()
                     if r.name == "engine.decode")
        runs.append(([r.generated for r in reqs], launched - landed))
        assert reqs[0].finish_reason == "eos"
        assert eng.cache.state_pool.slots_used == 0
    assert runs[0][0] == runs[1][0]
    # the run with a launch in flight overran the EOS; the other did not
    assert runs[0][1] >= 1 and runs[1][1] == 0


def test_the_decode_kernel_serves_what_the_xla_route_serves(params):
    out = []
    for route in ("xla", "decode"):
        with routing_override(decode=route):
            eng = engine(params)
            reqs = [eng.submit(p, 7) for p in prompts(11, (8, 30))]
            eng.run()
            out.append([r.generated for r in reqs])
    assert out[0] == out[1]


# -- (f) the multipliers, the tied head, no positions -------------------------

def test_the_multipliers_by_hand(params):
    block = CFG.block()
    tokens = jnp.asarray([[3, 9]], jnp.int32)
    x0 = block.embed(params, tokens, None)
    np.testing.assert_allclose(x0, 12.0 * params["embed"][tokens])
    # both residual branches times 0.22
    x = jnp.ones((1, 2, 64))
    np.testing.assert_allclose(block._residual(x, 2.0 * x), 1.44 * x,
                               rtol=1e-6)
    # logits through the embedding transposed, over 8
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 64))
    np.testing.assert_allclose(block.logits(params, h),
                               h @ params["embed"].T / 8.0, rtol=1e-6)
    assert "head" not in params
    # scores times 1/8 here (1/64 as published), not 8 ** -0.5: the
    # attention layer hands the decoder its own scale
    seen = {}

    def attend(q, k, v, scale=None):
        seen["scale"] = scale
        return jnp.zeros(q.shape[:-2] + (64,), q.dtype)

    block.layer(params["layers"][5], 5, x, None, attend)
    assert seen["scale"] == 0.125
    full = config(attention_multiplier=0.015625)
    assert full.attention_multiplier == 1 / 64


def test_no_position_enters_anywhere(params):
    """The same tokens at positions 0.. and at positions 100.. give the
    same logits: nothing reads ``positions``."""
    dec = PagedDecoder(CFG)
    seq = prompts(12, (16,))[0]
    row = lambda a: jnp.asarray(np.asarray(a, np.int32)[None])
    at = lambda first: dec.prefill(
        params, row(seq), row(np.ones(16)),
        row(np.arange(first, first + 16)))[0]
    np.testing.assert_array_equal(at(0), at(100))


def test_the_reference_reads_the_published_keys():
    assert SHAPE.head_dim == 8 and SHAPE.d_inner == 128
    assert SHAPE.conv_dim == 160
    layout = ref.param_layout(MODEL)
    assert layout["layers"][0]["win"][0] == (64, 128 + 160 + 8)
    assert layout["layers"][5]["wk"][0] == (64, 16)
    assert "head" not in layout
    for bad in (dict(mamba_n_groups=2), dict(tie_word_embeddings=False),
                dict(mamba_expand=4)):
        with pytest.raises(ValueError):
            ref.model_shape({**MODEL, **bad})


# -- (g) what it refuses ------------------------------------------------------

@pytest.mark.parametrize("option", [
    dict(tp=2), dict(kv_quant="int8"), dict(prefix_sharing=True),
    dict(spec=SpecConfig(k=2)), dict(prefill_only=True),
    dict(kv_import=True)])
def test_an_option_granite_hybrid_does_not_carry_raises_at_construction(
        params, option):
    with pytest.raises(ValueError, match="granite_hybrid.*What granite"):
        engine(params, **option)


def test_a_slot_is_not_shipped(params):
    eng = engine(params)
    req = eng.submit(prompts(13, (6,))[0], 4)
    eng.step()
    with pytest.raises(ValueError, match="slots of a state pool"):
        eng.export_request(req.rid)
    with pytest.raises(ValueError, match="slots of a state pool"):
        eng.adopt_prefilled({}, [], 0)


# -- the ring and the executables ---------------------------------------------

def test_the_ring_and_the_executables_name_what_the_model_adds(params):
    eng = engine(params)
    PHASE_RING.clear()
    eng.submit(prompts(14, (12,))[0], 3)
    eng.submit(prompts(14, (40,))[0], 3)
    eng.run()
    ring = PHASE_RING.snapshot()
    steps = [r for r in ring if r.name == "engine.step"]
    assert all(r.attrs["state_slots"] == 5 for r in steps)
    assert max(r.attrs["state_slots_held"] for r in steps) == 2
    assert steps[-1].attrs["state_slots_held"] == 0
    prefills = [r.attrs for r in ring if r.name == "engine.prefill"]
    # a whole row, then three chunks: the first from zero
    assert [p["state_in"] for p in prefills] == [0, 0, 1, 1]
    decodes = [r for r in ring if r.name == "engine.decode"]
    assert all(r.attrs["state_rows"] == r.attrs["rows"] for r in decodes)
    lowered = eng.analysis_executables()
    text = lowered["decode"].as_text(debug_info=True)
    for scope in ("ssm_in_proj", "ssm_update", "ssm_gate_norm",
                  "ssm_out_proj", "attn_nope", "mlp"):
        assert scope in text, scope
    # a row or a chunk: the convolution and the chunked scan
    for name in ("prefill", "chunk"):
        text = lowered[name].as_text(debug_info=True)
        assert "ssm_conv" in text and "ssm_scan" in text, name
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    with routing_override(decode="decode"):
        fresh = engine(params)      # a trace is cached with its route
        structs = fresh._executable_arg_structs()["decode"]
        walk(jax.make_jaxpr(fresh._exec_defs["decode"][0])(*structs).jaxpr)
    assert names.count("ssm_decode_update") == 9
    assert names.count("flash_decode") == 1
