"""Device time by the program's own named scopes (ISSUE 37,
``apex_tpu.telemetry.scopes``): the path normaliser on recorded
``op_name`` strings, the parser on a hand-written optimized-HLO module,
the registry (a dict entry at warm-up, nothing compiled, nothing kept
alive), the maps of a tiny engine of each block and of a tiny flagship
step, and ``by_scope`` / the CLI over hand-made and recorded events."""

import gc
import inspect
import json
import os
import re
import weakref

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.analysis import hot_path_guard
from apex_tpu.serving import (AfmoeConfig, DeepseekV2Config,
                              GraniteHybridConfig, Lfm2MoeConfig,
                              NemotronHConfig, ServingEngine,
                              ServingModelConfig, SimClock)
from apex_tpu.telemetry import scopes

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(REPO, "benchmark", "tests")


@pytest.fixture(autouse=True)
def empty_registry():
    scopes.clear()
    yield
    scopes.clear()


# -- the path normaliser -------------------------------------------------------

OP_NAMES = {
    "forward":
        ("jit(_decode)/layer/mla_absorb/dot_general", "layer/mla_absorb"),
    "a jit inside a scope":
        ("jit(_decode)/layer/moe_shared/jit(silu)/mul", "layer/moe_shared"),
    "no scope":
        ("jit(_decode)/jit(floor_divide)/rem", ""),
    "only a primitive":
        ("reduce_sum", ""),
    "a scope nested in a scope":
        ("jit(_decode)/layer/attn_latent/mla_absorb/...hd,chd->...hc/"
         "dot_general", "layer/attn_latent/mla_absorb"),
    "jvp":
        ("jit(inner)/fwd_bwd/jvp()/while/body/closed_call/dot_general",
         "fwd_bwd"),
    "transpose(jvp(...)) keeps the scope of its forward":
        ("jit(inner)/transpose(jvp(fwd_bwd))/layer/mul", "fwd_bwd/layer"),
    "the scope named twice by the transpose":
        ("jit(inner)/fwd_bwd/transpose(fwd_bwd)/jvp()/mul", "fwd_bwd"),
    "checkpoint":
        ("jit(inner)/fwd_bwd/transpose(jvp())/while/body/closed_call/"
         "checkpoint/rematted_computation/add", "fwd_bwd"),
    "while/body":
        ("jit(step)/scan_layers/while/body/layer/mlp/dot_general",
         "scan_layers/layer/mlp"),
    "a transform around a jit":
        ("jit(inner)/fwd_bwd/jvp(jit(_take))/jit(_where)/select_n",
         "fwd_bwd"),
    "a Pallas call":
        ("jit(_decode)/layer/attn_latent/jit(_flash_decode_latent_pallas)/"
         "flash_decode_latent/pallas_call",
         "layer/attn_latent/flash_decode_latent"),
    "names XLA merged":
        ("jit(inner)/fwd_bwd/transpose(fwd_bwd)/jvp()/broadcast_in_dim;"
         "jit(inner)/zero_pack/reshape", "fwd_bwd"),
    "a conditional's branch":
        ("jit(f)/outer/cond/branch_1_fun/inner/add", "outer/inner"),
    "the optimizer":
        ("jit(inner)/zero_update/mul", "zero_update"),
    "made by the compiler":
        ("ragged-dot-none", ""),
}


@pytest.mark.parametrize("case", sorted(OP_NAMES))
def test_scope_path_of_recorded_op_names(case):
    op_name, want = OP_NAMES[case]
    assert scopes.scope_path(op_name) == want


# -- the parser on a hand-written module ---------------------------------------

HLO = '''HloModule jit__decode, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,64], param_1: bf16[64,64]) -> bf16[8,64] {
  %param_0 = bf16[8,64]{1,0} parameter(0)
  %param_1 = bf16[64,64]{1,0} parameter(1)
  %convert.3 = f32[8,64]{1,0} convert(%param_0), metadata={op_name="jit(_decode)/layer/mul"}
  %dot.1 = bf16[8,64]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(_decode)/layer/mla_q/dot_general"}
  ROOT %add.9 = bf16[8,64]{1,0} add(%dot.1, %param_0), metadata={op_name="jit(_decode)/layer/add"}
}

%fused_computation.2 (param_0.1: f32[64]) -> bf16[8,8] {
  %param_0.1 = f32[64]{0} parameter(0)
  %slice.4 = f32[64]{0} slice(%param_0.1), slice={[0:64]}, metadata={op_name="jit(inner)/zero_unpack/slice"}
  %convert.5 = bf16[64]{0} convert(%slice.4)
  ROOT %bitcast.6 = bf16[8,8]{1,0} bitcast(%convert.5)
}

%body (param: (s32[], bf16[8,64], bf16[8,64])) -> (s32[], bf16[8,64], bf16[8,64]) {
  %param = (s32[], bf16[8,64]{1,0}, bf16[8,64]{1,0}) parameter(0)
  %get-tuple-element.7 = bf16[8,64]{1,0} get-tuple-element(%param), index=1
  %dynamic-update-slice.2 = bf16[8,64]{1,0} dynamic-update-slice(%get-tuple-element.7, %get-tuple-element.7)
  ROOT %tuple.3 = (s32[], bf16[8,64]{1,0}, bf16[8,64]{1,0}) tuple(%get-tuple-element.7, %dynamic-update-slice.2, %dynamic-update-slice.2)
}

%cond (param.1: (s32[], bf16[8,64], bf16[8,64])) -> pred[] {
  %param.1 = (s32[], bf16[8,64]{1,0}, bf16[8,64]{1,0}) parameter(0)
  ROOT %constant.8 = pred[] constant(true)
}

ENTRY %main.90 (x: bf16[8,64], w: bf16[64,64], flat: f32[64]) -> (bf16[8,64], bf16[8,8]) {
  %x = bf16[8,64]{1,0} parameter(0)
  %w = bf16[64,64]{1,0} parameter(1)
  %flat = f32[64]{0} parameter(2)
  %copy-start.1 = (bf16[64,64]{1,0}, bf16[64,64]{1,0:S(1)}, u32[]) copy-start(%w)
  %copy-done.1 = bf16[64,64]{1,0:S(1)} copy-done(%copy-start.1)
  %fusion.93 = bf16[8,64]{1,0} fusion(%x, %copy-done.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(_decode)/layer/add"}
  %flash_decode_latent.24 = bf16[8,64]{1,0} custom-call(%fusion.93), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode)/layer/attn_latent/jit(_flash_decode_latent_pallas)/flash_decode_latent/pallas_call"}
  %ragged-dot-none.2 = bf16[8,64]{1,0} custom-call(%flash_decode_latent.24, %copy-done.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %multiply_fusion.4 = bf16[8,64]{1,0} multiply(%ragged-dot-none.2, %ragged-dot-none.2), metadata={op_name="jit(_decode)/layer/moe_experts/mul"}
  %ragged-dot-none.3 = bf16[8,64]{1,0} custom-call(%multiply_fusion.4, %copy-done.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %add.11 = bf16[8,64]{1,0} add(%ragged-dot-none.3, %x), metadata={op_name="jit(_decode)/layer/add"}
  %tuple.80 = (s32[], bf16[8,64]{1,0}, bf16[8,64]{1,0}) tuple(%add.11, %add.11, %add.11)
  %while.4 = (s32[], bf16[8,64]{1,0}, bf16[8,64]{1,0}) while(%tuple.80), condition=%cond, body=%body, metadata={op_name="jit(_decode)/copy_out/while"}
  %get-tuple-element.9 = bf16[8,64]{1,0} get-tuple-element(%while.4), index=2
  %argmax.1 = bf16[8,64]{1,0} negate(%get-tuple-element.9), metadata={op_name="jit(_decode)/argmax"}
  %fusion.94 = bf16[8,8]{1,0} fusion(%flat), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(inner)/convert_element_type"}
  ROOT %tuple.81 = (bf16[8,64]{1,0}, bf16[8,8]{1,0}) tuple(%argmax.1, %fusion.94)
}
'''


def test_instruction_scopes_of_a_hand_written_module():
    got = scopes.instruction_scopes(HLO)
    # a fusion takes the scope of its dot, and says that its parts came
    # from more than one
    assert got["%fusion.93"] == ("layer/mla_q", True)
    # a root the compiler made: the scope its fused instructions have
    assert got["%fusion.94"] == ("zero_unpack", False)
    # a Pallas call keeps its kernel's name, once
    assert got["%flash_decode_latent.24"] == (
        "layer/attn_latent/flash_decode_latent", False)
    # made by the compiler: its users' scope, an operand's where that
    # is the same said more precisely; then the kernel's name
    assert got["%ragged-dot-none.2"][0] == "layer/moe_experts/ragged-dot-none"
    assert got["%ragged-dot-none.3"][0] == "layer/moe_experts/ragged-dot-none"
    # a weight's prefetch belongs to who reads it
    assert got["%copy-done.1"][0] == "layer"
    assert got["%copy-start.1"][0] == "layer"
    # a while's scope is handed down to what the compiler put in its body
    assert got["%while.4"][0] == "copy_out"
    assert got["%dynamic-update-slice.2"][0] == "copy_out"
    assert got["%tuple.80"][0] == "copy_out"
    # program code under no scope stays under none
    assert got["%argmax.1"] == ("", False)
    # what runs inside a fusion is no event of its own
    assert "%dot.1" not in got and "%slice.4" not in got
    assert scopes.kernel_name("%fusion.3.remat2") == "fusion"


def test_computations_with_tuple_parameters_are_split():
    comps = scopes.computations(HLO)
    assert list(comps) == ["fused_computation.1", "fused_computation.2",
                           "body", "cond", "main.90"]
    assert "%dynamic-update-slice.2" in comps["body"]
    assert "%dynamic-update-slice.2" not in comps["fused_computation.2"]


# -- by_scope, dump / load, the CLI ------------------------------------------------

def test_by_scope_counts_nested_events_once():
    sm = scopes.ScopeMap(None, {
        "%while.4": ("copy_out", False),
        "%dynamic-update-slice.2": ("copy_out", False),
        "%fusion.93": ("layer/mla_q", True),
        "%flash_decode_latent.24": ("layer/attn_latent/flash_decode_latent",
                                    False)})
    ops = [("%fusion.93 = bf16[8,64] fusion(...)", 0.0, 100.0),
           ("%while.4 = (...) while(...)", 100.0, 1000.0),
           ("%dynamic-update-slice.2 = bf16[8,64] d-u-s(...)", 150.0, 300.0),
           ("%unknown.1 = f32[] add(...)", 500.0, 200.0),
           ("%flash_decode_latent.24 = bf16[8,64] custom-call()", 1100.0,
            400.0),
           ("%fusion.93 = bf16[8,64] fusion(...)", 1500.0, 100.0)]
    rows = {r.scope: r for r in scopes.by_scope(ops, sm)}
    # the while's 1000 ns less the 500 its body's events cover, and the
    # body's own 300: nothing twice
    assert rows["copy_out"].seconds == pytest.approx(800e-9)
    assert rows["copy_out"].runs == 2
    assert rows[scopes.UNKNOWN].seconds == pytest.approx(200e-9)
    assert rows["layer/mla_q"].seconds == pytest.approx(200e-9)
    assert rows["layer/mla_q"].mixed_seconds == pytest.approx(200e-9)
    assert sum(r.seconds for r in rows.values()) == pytest.approx(1600e-9)
    by_depth = {r.scope: r.seconds for r in scopes.by_scope(ops, sm, depth=1)}
    assert by_depth["layer"] == pytest.approx(600e-9)
    assert [r.scope for r in scopes.by_scope(ops, sm)][0] == "copy_out"


def tiny_gpt_engine(**kw):
    cfg = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                             num_layers=2, max_position=96)
    return ServingEngine(cfg, None, num_pages=32, page_size=8, max_batch=2,
                         clock=SimClock(), **kw)


def test_dump_and_load_round_trip(tmp_path):
    eng = tiny_gpt_engine()
    eng._register_scopes()
    path = str(tmp_path / "scopes.json")
    scopes.dump(path, ["jit__decode"])
    loaded = scopes.load(path)
    resolved = scopes.scope_maps(["jit__decode"])
    assert list(loaded) == ["jit__decode"]
    assert [m.variant for m in loaded["jit__decode"]] == [None]
    assert loaded["jit__decode"][0].instructions == \
        resolved["jit__decode"][0].instructions
    with open(path) as f:
        doc = json.load(f)
    assert set(doc["jit__decode"][0]) == {"variant", "instructions"}


def test_cli_prints_a_table_for_a_recorded_profile(tmp_path, capsys):
    from jax.profiler import ProfileData

    from apex_tpu.telemetry.__main__ import main

    with open(os.path.join(RECORDED, "recorded_trace.txt")) as f:
        xspace = ProfileData.text_proto_to_serialized_xspace(f.read())
    pb = tmp_path / "recorded.xplane.pb"
    pb.write_bytes(xspace)
    maps = os.path.join(RECORDED, "recorded_trace.scopes.json")
    assert main(["scopes", "--maps", maps, str(pb)]) == 0
    out = capsys.readouterr().out
    assert "jit__decode [2048]: 1 runs, 45.367 ms a run" in out
    assert "layer/attn_full/_decode" in out and "(no scope)" in out
    assert main(["scopes", "--maps", maps, "--executable", "jit__chunk",
                 str(pb)]) == 1
    assert main(["scopes", "--maps", maps, "--depth", "1", str(pb)]) == 0
    assert "layer/attn_full" not in capsys.readouterr().out


# -- the registry ----------------------------------------------------------------

def test_register_is_a_dict_entry_and_compiles_nothing(monkeypatch):
    """``warmup()`` compiles as much with the registry as without it,
    and a registered entry is resolved by ``scope_maps`` alone."""
    def compiles(eng):
        with hot_path_guard("warmup", max_recompiles=10 ** 6, transfers=None,
                            tripwire=False) as guard:
            eng.warmup()
        return guard.recompiles

    compiles(tiny_gpt_engine())     # what engines share compiles once
    scopes.clear()
    with_registry = compiles(tiny_gpt_engine())
    assert sorted(scopes.registered()) == [
        ("jit__decode", None), ("jit__prefill", "96")]
    assert all(e.resolved is None for e in scopes._REGISTRY.values())
    scopes.clear()
    monkeypatch.setattr(scopes, "register", lambda *a, **k: None)
    without = compiles(tiny_gpt_engine())
    assert scopes.registered() == []
    assert with_registry == without > 0


def test_an_entry_keeps_neither_the_engine_nor_its_arrays_alive():
    eng = tiny_gpt_engine()
    eng.warmup()
    refs = [weakref.ref(eng), weakref.ref(eng.cache.k),
            weakref.ref(jax.tree_util.tree_leaves(eng.params)[0])]
    assert ("jit__decode", None) in scopes.registered()
    del eng
    gc.collect()
    assert [r() for r in refs] == [None, None, None]
    # and the entry still resolves, the engine gone
    (decode,) = scopes.scope_maps(["jit__decode"])["jit__decode"]
    assert decode.instructions and decode.hlo_bytes > 0


def test_registering_again_replaces_unless_it_is_the_same():
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((4,), jnp.float32)
    scopes.register("jit_f", f, (x,))
    scopes.scope_maps()
    first = scopes._REGISTRY[("jit_f", None)]
    assert first.resolved is not None
    assert first.args == (jax.ShapeDtypeStruct((4,), jnp.float32),)
    scopes.register("jit_f", f, (x,))          # a re-trace: kept
    assert scopes._REGISTRY[("jit_f", None)] is first
    scopes.register("jit_f", f, (jnp.zeros((8,), jnp.float32),))
    assert scopes._REGISTRY[("jit_f", None)].resolved is None
    scopes.register("jit_f", f, (x,), variant=4)
    assert sorted(scopes.registered(), key=str) == [
        ("jit_f", "4"), ("jit_f", None)]
    assert scopes.executable_name(f) == "jit__lambda_"


def test_the_hot_path_never_calls_into_scopes():
    """Registration is warm-up's: no function of a step, a decode
    launch, a prefill or a chunk names the module."""
    for fn in (ServingEngine.step, ServingEngine._step_body,
               ServingEngine._step_phases, ServingEngine._decode_batch,
               ServingEngine._land, ServingEngine._prefill_request,
               ServingEngine._chunk_step, ServingEngine._verify_batch):
        assert "scopes" not in inspect.getsource(fn), fn.__name__
    assert "scopes.register" in inspect.getsource(
        ServingEngine._register_scopes)
    assert "_register_scopes" in inspect.getsource(ServingEngine.warmup)


# -- a tiny engine of each block ---------------------------------------------------

def _afmoe():
    return AfmoeConfig(
        vocab_size=96, hidden_size=32, num_heads=6, num_kv_heads=2,
        head_dim=8, layer_types=("sliding_attention", "full_attention"),
        num_dense_layers=1, intermediate_size=64, moe_intermediate_size=24,
        num_experts=16, experts_held=(4, 8), top_k=3, route_scale=2.448,
        sliding_window=20, dtype=jnp.float32)


def _deepseek_v2():
    return DeepseekV2Config(
        vocab_size=96, hidden_size=32, num_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, num_layers=2, first_k_dense_replace=1,
        intermediate_size=64, moe_intermediate_size=24, n_routed_experts=16,
        experts_held=(0, 2), top_k=3, n_group=8, topk_group=3,
        routed_scaling_factor=16.0, n_shared_experts=2,
        rope_original_max=32, dtype=jnp.float32)


def _granite_hybrid():
    return GraniteHybridConfig(
        vocab_size=96, hidden_size=64, num_heads=8, num_kv_heads=2,
        layer_types=("mamba", "attention"), intermediate_size=128,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
        mamba_chunk_size=8, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.125,
        logits_scaling=8.0, rms_norm_eps=1e-5)


def _lfm2_moe():
    return Lfm2MoeConfig(
        vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        experts_held=(0, 8), top_k=2)


def _nemotron_h():
    return NemotronHConfig(
        vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, layer_types=("mamba", "moe", "attention", "moe"),
        mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=2, mamba_d_state=16,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
        num_experts=16, experts_held=(0, 8), top_k=4, moe_latent_size=32,
        route_scale=5.0, mamba_chunk_size=8)


BLOCKS = {
    "gpt": (lambda: ServingModelConfig(
        vocab_size=64, hidden_size=32, num_heads=4, num_layers=2,
        max_position=96), {},
        {"embedding", "layer", "head"}),
    "afmoe": (_afmoe, {"window_pages": 24, "max_pages_per_request": 16,
                       "prefill_budget": 16},
              {"attn_window", "attn_full", "moe_router", "moe_experts",
               "moe_shared", "layer", "head"}),
    "deepseek_v2": (_deepseek_v2, {"max_pages_per_request": 16,
                                   "prefill_budget": 16},
                    {"mla_q", "mla_kv_down", "mla_absorb", "mla_out",
                     "attn_latent", "mlp", "moe_router", "moe_experts",
                     "moe_shared", "layer", "head"}),
    "granite_hybrid": (_granite_hybrid, {"max_pages_per_request": 16,
                                         "prefill_budget": 16},
                       {"ssm_in_proj", "ssm_update", "ssm_gate_norm",
                        "ssm_out_proj", "attn_nope", "mlp", "layer", "head"}),
    "lfm2_moe": (_lfm2_moe, {"max_pages_per_request": 16,
                             "prefill_budget": 16},
                 {"conv_in_proj", "short_conv", "conv_out_proj", "attn_full",
                  "mlp", "moe_router", "moe_experts", "layer", "head"}),
    "nemotron_h": (_nemotron_h, {"max_pages_per_request": 16,
                                 "prefill_budget": 16},
                   {"ssm_in_proj", "ssm_update", "ssm_gate_norm",
                    "ssm_out_proj", "attn_nope", "moe_router",
                    "moe_latent_down", "moe_experts", "moe_latent_up",
                    "moe_shared", "layer", "head"}),
}
#: each expert block's two grouped kernels, by name
GROUPED_KERNELS = {
    "afmoe": ("grouped_swiglu_gate_up", "grouped_swiglu_down"),
    "deepseek_v2": ("grouped_swiglu_gate_up", "grouped_swiglu_down"),
    "lfm2_moe": ("grouped_swiglu_gate_up", "grouped_swiglu_down"),
    "nemotron_h": ("grouped_relu2_up", "grouped_swiglu_down"),
}
#: of a tiny engine's decode instructions, at most this share is a fusion
#: of parts from more than one scope (the CPU's fusions; measured 0.02-0.07)
MIXED_SHARE = 0.15


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_a_tiny_engine_resolves_a_decode_map_with_its_blocks_scopes(block):
    make, kw, want = BLOCKS[block]
    cfg = make()
    eng = ServingEngine(cfg, cfg.init_params(3) if block != "gpt" else None,
                        num_pages=40, page_size=8, max_batch=3,
                        clock=SimClock(), **kw)
    eng._register_scopes()
    names = {name for name, _ in scopes.registered()}
    assert {"jit__decode", "jit__prefill"} <= names
    assert ("jit__chunk" in names) == (eng._chunk_fn is not None)
    (decode,) = scopes.scope_maps(["jit__decode"])["jit__decode"]
    elements = {e for scope, _ in decode.instructions.values()
                for e in scope.split("/")}
    assert want <= elements, want - elements
    mixed = sum(m for _, m in decode.instructions.values())
    assert mixed <= MIXED_SHARE * len(decode.instructions)
    # only what was asked for was resolved
    assert scopes._REGISTRY[("jit__prefill", str(eng.prefill_widths[0]))
                            ].resolved is None


def test_the_lfm2_blocks_three_executables_leave_nothing_under_no_scope():
    """ISSUE 39: every instruction of the decode step, of each prefill
    row and of the chunk comes from some named scope, so that the time
    of a run adds up by scope with nothing left over."""
    make, kw, _ = BLOCKS["lfm2_moe"]
    cfg = make()
    eng = ServingEngine(cfg, cfg.init_params(3), num_pages=40, page_size=8,
                        max_batch=3, clock=SimClock(), **kw)
    eng._register_scopes()
    names = ["jit__decode", "jit__prefill", "jit__chunk"]
    maps = scopes.scope_maps(names)
    assert sorted(maps) == sorted(names)
    structs = eng._executable_arg_structs()
    for name, key in zip(names, ("decode", "prefill", "chunk")):
        # what runs: the entry and the bodies of its loops (a reducer's
        # or a comparator's instructions are no events of their own);
        # parameters, constants and tuples are no work
        text = jax.jit(eng._exec_defs[key][0]).trace(
            *structs[key]).lower().compile().as_text()
        comps = scopes.computations(text)
        run = ({re.search(r"^ENTRY %?(\S+)", text, re.M).group(1)}
               | set(re.findall(r"(?:body|condition)=%?([\w.\-]+)", text)))
        heads = {head for comp in run
                 for head, _, opcode, _ in scopes._instructions(comps[comp])
                 if opcode not in {"parameter", "constant", "tuple",
                                   "get-tuple-element", "bitcast"}}
        (m,) = [m for m in maps[name]
                if m.variant in (None, str(eng.prefill_widths[-1]))]
        assert heads and heads <= set(m.instructions), name
        bare = sorted(h for h in heads if not m.instructions[h][0])
        assert not bare, (name, bare[:10])


@pytest.mark.parametrize("block", sorted(GROUPED_KERNELS))
def test_the_grouped_kernels_sit_under_moe_experts(block):
    """In each executable of every expert block the grouped
    product's two kernels are under ``moe_experts`` with their own names
    last, and what is under no scope is an op the step's own code made
    outside every layer (its ``op_name`` the bare jit's), never one the
    compiler made without a name inside the expert layer."""
    make, kw, _ = BLOCKS[block]
    cfg = make()
    eng = ServingEngine(cfg, cfg.init_params(3), num_pages=40, page_size=8,
                        max_batch=3, clock=SimClock(), **kw)
    eng._register_scopes()
    names = ["jit__decode", "jit__prefill", "jit__chunk"]
    maps = scopes.scope_maps(names)
    structs = eng._executable_arg_structs()
    for name, key in zip(names, ("decode", "prefill", "chunk")):
        (m,) = [m for m in maps[name]
                if m.variant in (None, str(eng.prefill_widths[-1]))]
        kernels = {scope for scope, _ in m.instructions.values()
                   if "grouped_" in scope}
        assert kernels == {"layer/moe_experts/" + k
                           for k in GROUPED_KERNELS[block]}, name
        text = jax.jit(eng._exec_defs[key][0]).trace(
            *structs[key]).lower().compile().as_text()
        # what runs: the entry and the bodies of its loops; parameters,
        # constants and tuples are no work
        comps = scopes.computations(text)
        run = ({re.search(r"^ENTRY %?(\S+)", text, re.M).group(1)}
               | set(re.findall(r"(?:body|condition)=%?([\w.\-]+)", text)))
        bare = [head for comp in run
                for head, _, opcode, line in scopes._instructions(comps[comp])
                if opcode not in {"parameter", "constant", "tuple",
                                  "get-tuple-element", "bitcast"}
                and not m.instructions[head][0]
                and not re.search(r'op_name="jit\(\w+\)/[\w.]+"', line)]
        assert not bare, (name, bare[:5])


def test_a_prefill_row_is_registered_at_every_width():
    cfg = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                             num_layers=1, max_position=256)
    eng = ServingEngine(cfg, None, num_pages=64, page_size=8, max_batch=2,
                        clock=SimClock())
    assert len(eng.prefill_widths) == 2
    eng._register_scopes()
    widths = {v for n, v in scopes.registered() if n == "jit__prefill"}
    assert widths == {str(w) for w in eng.prefill_widths}
    for w in eng.prefill_widths:
        row = scopes._REGISTRY[("jit__prefill", str(w))].args[1]
        assert row.shape == (1, w)
        assert eng._executable_arg_structs(w)["prefill"][1].shape == (1, w)


# -- a tiny flagship step ------------------------------------------------------------

def test_a_tiny_flagship_steps_map_holds_the_optimizers_phases():
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing import build_flagship_train_step
    from apex_tpu.transformer.testing.standalone_gpt import GPTConfig

    cfg = GPTConfig(num_layers=2, hidden_size=64, num_attention_heads=2,
                    vocab_size=128, max_position_embeddings=32, bf16=True)
    try:
        fs = build_flagship_train_step(cfg, plan="bf16_fit",
                                       devices=jax.devices()[:1])
        assert scopes.registered() == []   # a batch's shape is not known yet
        tokens = jnp.zeros((2, 32), jnp.int32)
        params, opt_state, loss = fs.step(fs.params, fs.opt_state, tokens,
                                          tokens)
        assert scopes.registered() == [("jit_inner", None)]
        entry = scopes._REGISTRY[("jit_inner", None)]
        assert entry.jitted is fs.step
        assert entry.args[2].shape == (2, 32)
        leaf = weakref.ref(jax.tree_util.tree_leaves(params)[0])
        del params, opt_state, loss, fs
        gc.collect()
        assert leaf() is None
        (step,) = scopes.scope_maps(["jit_inner"])["jit_inner"]
        found = {scope.split("/")[0]
                 for scope, _ in step.instructions.values()}
        assert {"fwd_bwd", "zero_pack", "zero_update", "zero_unpack"} <= found
    finally:
        parallel_state.destroy_model_parallel()
