"""The sixth architecture behind ``ServingEngine``: a
``nemotron_h`` decoder with a latent expert layer (NVIDIA Nemotron 3
Super: ONE mixer a layer; Mamba-2 layers whose heads read two groups of
B and C, a NoPE grouped-query layer, and expert layers that keep nothing
of a request, their squared-ReLU experts run in a latent beside a
squared-ReLU shared expert, 8 of 16 experts held, top-4) whose requests
own a slot of recurrent state beside their pages, against its plain
reference, at CPU size (d = 64, 2 groups of 2 heads of 16, state 16,
latent 32, the pattern ``MEMEM*E``), float32, on the XLA routes and the
kernels in interpret mode.

Tolerances: program and reference are both float32; they differ by the
order of reductions (the chunked scan against the token-by-token
recurrence, the paged kernel's online softmax, the grouped products,
XLA's fusions), about 1e-6 of logits of order 1.  2e-4 leaves two
orders of magnitude above that and lies two below what one bfloat16
rounding of the activations moves a logit by (about 1e-2).  The share
test adds four float32 partial layers: 1e-5 of outputs of order 1."""

import json
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

from reference import nemotron_h_serve as ref  # noqa: E402

from apex_tpu.ops.attention import routing_override  # noqa: E402
from apex_tpu.serving import (NemotronHConfig, ServingEngine,  # noqa: E402
                              SimClock, SpecConfig)
from apex_tpu.serving.experts import expert_layer  # noqa: E402
from apex_tpu.serving.kv_cache import PagedKVCache, StatePool  # noqa: E402
from apex_tpu.serving.model import PagedDecoder, StateIO  # noqa: E402
from apex_tpu.telemetry import PHASE_RING  # noqa: E402

PS = 8
TOL = 2e-4
MODEL = dict(
    hybrid_override_pattern="MEMEM*E", num_hidden_layers=7, vocab_size=96,
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
    ssm_state_size=16, conv_kernel=4, chunk_size=8, expand=1,
    moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=64, n_routed_experts=16,
    num_experts_per_tok=4, routed_scaling_factor=5, norm_topk_prob=True,
    mlp_hidden_act="relu2", n_group=1, topk_group=1,
    layer_norm_epsilon=1e-5, tie_word_embeddings=False, use_conv_bias=True,
    experts_held=(0, 8))
SHAPE = ref.model_shape(MODEL)
CFG = NemotronHConfig.from_published(MODEL, experts_held=(0, 8))

#: NVIDIA-Nemotron-3-Super-120B-A12B-BF16's row of the model-configs
#: catalog (``config``), as published at
#: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEM"
                               "EMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}


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

def test_the_families_of_layers_and_layers_that_keep_nothing():
    dec = PagedDecoder(CFG)
    assert CFG.layer_types == ("mamba", "moe", "mamba", "moe", "mamba",
                               "attention", "moe")
    assert (dec.full_layers, dec.window_layers, dec.n_state_layers) \
        == (1, 0, 3)
    assert dec.bare_layers == frozenset({1, 3, 6})
    assert dec.pool_index == (0, 0, 1, 1, 2, 0, 2)
    assert dec.state_planes == ("ssm", "conv") and dec.kv_stacks == 4
    assert CFG.state_shape == (16, 64)
    # x, then two groups' B and C, three rows end to end
    assert CFG.conv_dim == 64 + 2 * 2 * 16
    assert CFG.tail_shape == (1, 3 * 128)
    assert (CFG.head_dim, dec.page_head_dim) == (16, 128)


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
        logits, k, v, state, tail, counters = dec.prefill(
            params, row(seq[:C]), row(np.ones(C)), row(np.arange(C)))
        # the attention layer alone keeps K/V: the expert layers nothing
        assert k.shape == (1, 1, S, 2, 128)
        assert state.shape == (3, 1) + CFG.state_shape
        assert tail.shape == (3, 1) + CFG.tail_shape
        assert counters.shape == (4,)
        np.testing.assert_allclose(np.asarray(logits[0, :C]), want[:C],
                                   atol=TOL)
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
            assert len(out) == 6   # logits, K, V, states, tails, counters
            cache.k, cache.v, spool.ssm, spool.conv = out[1:5]
            np.testing.assert_allclose(np.asarray(out[0][0]), want[p],
                                       atol=TOL, err_msg=f"position {p}")
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
            cache.k, cache.v, spool.ssm, spool.conv = out[1:5]
            np.testing.assert_allclose(
                np.asarray(out[0][0, chunk - n:]), want[start:start + n],
                atol=TOL, err_msg=f"chunk at {start}")
    # ... and a whole row over the same tokens leaves the same state
    S = 48
    row = lambda a: jnp.asarray(np.pad(np.asarray(a, np.int32),
                                       (0, S - len(seq)))[None])
    _, _, _, state, tail, _ = dec.prefill(
        params, row(seq), row(np.ones(len(seq))), row(np.arange(len(seq))))
    np.testing.assert_allclose(np.asarray(spool.ssm[:, slot]),
                               np.asarray(state[:, 0]), atol=TOL)
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


def test_batched_decoding_equals_sequential_decoding(params):
    batched = engine(params)
    reqs = [batched.submit(p, 10) for p in prompts(9, (6, 21, 13))]
    batched.run()
    for req in reqs:
        alone = engine(params)
        one = alone.submit(req.prompt, 10)
        alone.run()
        assert one.generated == req.generated


def test_the_decode_kernel_serves_what_the_xla_route_serves(params):
    out = []
    for route in ("xla", "decode"):
        with routing_override(decode=route):
            eng = engine(params)
            reqs = [eng.submit(p, 7) for p in prompts(11, (8, 30))]
            eng.run()
            out.append([r.generated for r in reqs])
    assert out[0] == out[1]


def test_the_lane_dense_pool_serves_what_a_head_split_pool_serves(
        params, monkeypatch):
    """Two K/V heads fill no sublane tile: the page pool keeps a page
    ``[page_size, 2 * 128]`` and the kernel reads a head as a view.  The
    same engine over the same numbers stored ``[page_size, 2, 128]``
    serves the same tokens."""
    from apex_tpu.serving import kv_cache

    out = {}
    for layout in ("lane_dense", "heads"):
        if layout == "heads":
            monkeypatch.setattr(kv_cache, "lane_dense_pages",
                                lambda *a: False)
        with routing_override(decode="decode"):
            eng = engine(params)
            assert eng.cache.page_layout == layout
            reqs = [eng.submit(p, 7) for p in prompts(11, (8, 30))]
            eng.run()
        out[layout] = [r.generated for r in reqs]
    assert out["lane_dense"] == out["heads"]


# -- (b) the latent expert layer ----------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips of one layer, each holding a quarter of the 16
    experts: what each share's held experts give (routed over all 16,
    computed in the latent and brought up by ``latent_up``, which is
    linear), with the shared expert counted once, adds up to the
    reference's layer with every expert held."""
    whole = NemotronHConfig.from_published(MODEL, experts_held=(0, 16))
    moe = whole.init_params(6)["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64), jnp.float32)
    total = 0.0
    for i, lo in enumerate(range(0, 16, 4)):
        share = dict(moe, experts=jax.tree_util.tree_map(
            lambda a: a[lo:lo + 4], moe["experts"]))
        if i:
            del share["shared"]        # every chip computes it alike
        y, _ = expert_layer(u, share, held=(lo, lo + 4), top_k=4,
                            route_scale=5.0, act="relu2")
        total = total + y
    want = ref.expert_layer(moe, u.reshape(-1, 64),
                            SHAPE._replace(held=(0, 16)))
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 64),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


def test_the_shared_expert_and_the_latent_pair_by_hand(params):
    """No pair on a held expert (the held range empty): the layer is the
    shared expert alone, ``relu(u Su)^2 Sd``; and the routed part runs
    between ``latent_down`` and ``latent_up``."""
    moe = params["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(3), (5, 64), jnp.float32)
    none = dict(moe, experts=jax.tree_util.tree_map(lambda a: a[:0],
                                                    moe["experts"]))
    got, load = expert_layer(u, none, held=(0, 0), top_k=4,
                             route_scale=5.0, act="relu2")
    with jax.default_matmul_precision("highest"):
        want = jnp.square(jax.nn.relu(u @ moe["shared"]["wu"])) \
            @ moe["shared"]["wd"]
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert load.shape == (0,)
    assert moe["latent_down"].shape == (64, 32)
    assert moe["experts"]["wu"].shape == (8, 32, 48)
    assert moe["experts"]["wd"].shape == (8, 48, 32)
    assert moe["latent_up"].shape == (32, 64)


# -- (c) what it refuses ------------------------------------------------------

@pytest.mark.parametrize("option", [
    dict(tp=2), dict(kv_quant="int8"), dict(prefix_sharing=True),
    dict(spec=SpecConfig(k=2)), dict(prefill_only=True),
    dict(kv_import=True)])
def test_an_option_nemotron_h_does_not_carry_raises_at_construction(
        params, option):
    with pytest.raises(ValueError, match="nemotron_h.*What Nemotron-H"):
        engine(params, **option)


def test_a_slot_is_not_shipped(params):
    eng = engine(params)
    req = eng.submit(prompts(13, (6,))[0], 4)
    eng.step()
    with pytest.raises(ValueError, match="slots of a state pool"):
        eng.export_request(req.rid)


# -- (d) the published keys, the configuration file, the reference ------------

def test_the_published_keys_give_the_published_sizes():
    """The catalog row, cut to its first period, read by the program's
    configuration and by the reference alike; what neither implements
    is refused."""
    cut = {**PUBLISHED, "num_hidden_layers": 11,
           "hybrid_override_pattern": PUBLISHED[
               "hybrid_override_pattern"][:11]}
    cfg = NemotronHConfig.from_published(cut, experts_held=(0, 128))
    assert cfg.layer_types.count("mamba") == 5
    assert cfg.layer_types.count("moe") == 5
    assert cfg.layer_types[7] == "attention"
    assert (cfg.d_inner, cfg.mamba_n_groups, cfg.conv_dim) \
        == (8192, 8, 8192 + 2 * 8 * 128)
    assert cfg.state_shape == (128, 8192)
    assert cfg.tail_shape == (1, 3 * 10240)
    assert (cfg.moe_latent_size, cfg.top_k, cfg.route_scale) \
        == (1024, 22, 5.0)
    m = ref.model_shape({**cut, "experts_held": (0, 128)})
    assert (m.hidden, m.heads, m.kv_heads, m.head_dim) == (4096, 32, 2, 128)
    assert (m.latent, m.expert_ffn, m.shared_ffn, m.router_width, m.held) \
        == (1024, 2688, 5376, 512, (0, 128))
    layout = ref.param_layout({**cut, "experts_held": (0, 128)})
    assert layout["layers"][0]["win"][0] == (4096, 8192 + 10240 + 128)
    assert layout["layers"][1]["moe"]["experts"]["wu"][0] \
        == (128, 1024, 2688)
    assert layout["layers"][1]["moe"]["shared"]["wu"][0] == (4096, 5376)
    assert layout["layers"][7]["wk"][0] == (4096, 256)
    for bad in (dict(hybrid_override_pattern="M-M*E-M-ME-"),
                dict(mlp_hidden_act="silu"), dict(n_group=8),
                dict(norm_topk_prob=False)):
        with pytest.raises(ValueError):
            NemotronHConfig.from_published({**cut, **bad})
    for bad in (dict(num_hidden_layers=12), dict(tie_word_embeddings=True),
                dict(expand=1), dict(mlp_hidden_act="silu")):
        with pytest.raises(ValueError):
            ref.model_shape({**cut, **bad})


def test_the_configuration_file_is_the_catalog_row_cut():
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-super-serve-ep4-l11.json")) as f:
        config = json.load(f)
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    for key, want in PUBLISHED.items():
        if key not in config["reduced"]:
            assert config[key] == want, key
    assert config["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert config["num_hidden_layers"] == 11
    assert (config["n_routed_experts"], config["vocab_size"],
            config["num_nextn_predict_layers"]) == (128, 32768, 0)
    assert config["published"]["num_hidden_layers"] == 88
    assert config["published"]["n_routed_experts"] == 512
    assert config["model"]["experts_held"] == [0, 128]
    assert config["model"]["router_width"] == 512
    assert config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B"
        "-BF16/blob/main/config.json")


def test_the_state_bf16_control_moves_the_first_layers_state():
    import weights

    params = ref.finish(weights.make(ref.param_layout(MODEL), 4,
                                     jnp.float32))
    tokens = jnp.asarray(prompts(8, (24,))[0], jnp.int32)
    exact = ref.first_layer_state(params, tokens, 24, SHAPE)
    rounded = ref.first_layer_state(params, tokens, 24, SHAPE, "state_bf16")
    assert 1e-4 < ref.state_gap(rounded, exact) < 1e-1
    assert ref.state_gap(exact, exact) == 0.0


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "nemotron_h_serve.py")) as f:
        assert "apex_tpu" not in f.read()


# -- (e) the ring and the executables -----------------------------------------

def test_the_ring_and_the_executables_name_what_the_model_adds(params):
    eng = engine(params)
    PHASE_RING.clear()
    eng.submit(prompts(14, (12,))[0], 3)
    eng.submit(prompts(14, (40,))[0], 3)
    eng.run()
    ring = PHASE_RING.snapshot()
    # a landed launch's counters: 3 expert layers, 4 pairs a row each of
    # which about half land on the 8 held experts
    decodes = [r.attrs for r in ring if r.name == "engine.decode"
               and "moe_pairs_held" in r.attrs]
    assert decodes
    for d in decodes:
        assert 1 <= d["moe_experts_touched"] <= min(3 * 8,
                                                    d["moe_pairs_held"])
        assert d["moe_pairs_held"] <= d["moe_rows_tiled"]
        assert d["moe_load_max"] <= d["moe_pairs_held"]
    prefills = [r.attrs for r in ring if r.name == "engine.prefill"]
    assert [p["state_in"] for p in prefills] == [0, 0, 1, 1]
    lowered = eng.analysis_executables()
    text = lowered["decode"].as_text(debug_info=True)
    for scope in ("ssm_in_proj", "ssm_update", "ssm_gate_norm",
                  "ssm_out_proj", "attn_nope", "moe_router",
                  "moe_latent_down", "moe_experts", "moe_latent_up",
                  "moe_shared", "grouped_relu2_up"):
        assert scope in text, scope
    assert "grouped_swiglu_gate_up" not in text
    chunk = lowered["chunk"].as_text(debug_info=True)
    for scope in ("ssm_conv", "ssm_scan"):
        assert scope in chunk, scope
