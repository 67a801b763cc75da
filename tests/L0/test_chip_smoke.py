"""Rehearse ``chip_smoke.py``'s legs at toy size on the CPU.

The smoke itself only runs on a TPU.  Its legs take their configuration
as arguments so that their control flow — build, step, serve, resubmit,
every check — is walked here before chip time is spent on it.  Nothing
here is a device measurement.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from apex_tpu.serving import ServingModelConfig  # noqa: E402
from apex_tpu.transformer.testing import gpt1p3b_config  # noqa: E402

TOY_GPT = dict(num_layers=2, hidden_size=256, num_attention_heads=2,
               vocab_size=256, max_position_embeddings=64)
TOY_SERVE = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                               num_layers=2, max_position=96)
TOY_TRAFFIC = dict(rate=1000.0, prompt_len=(4, 40), max_new=(2, 8),
                   page_size=8, max_batch=4)


def test_exits_nonzero_and_prints_no_result_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


def test_kernels_leg():
    out = chip_smoke.leg_kernels(
        batch=2, seq=64, heads=2, head_dim=16, block=32, pages=40,
        page_size=8, max_batch=2, pages_per_request=17)
    # off the TPU both sides of each comparison are the XLA route
    assert out["routes"] == {"qkv": "generic", "decode": "xla"}
    assert set(out["rel_l2"]) == {"qkv_fwd", "qkv_bwd", "decode", "verify",
                                  "chunk", "decode_int8",
                                  "decode_gqa_window"}
    assert max(out["rel_l2"].values()) == 0.0


@pytest.mark.parametrize("mesh_shape", [None, (4, 2, 1)])
def test_train_leg(mesh_shape):
    # all eight emulated devices: ZeRO dp=8, then the bucketed dp x tp step
    out = chip_smoke.leg_train(gpt1p3b_config(**TOY_GPT), batch_per_chip=2,
                               seq=64, steps=5, mesh_shape=mesh_shape)
    assert out["losses"][-1] < out["losses"][0]
    assert len(out["step_ms"]) == 5
    assert out["batch"] == 2 * out["mesh"]["data"]
    # the single-axis leg holds the step's first loss to the forward's
    assert (out["forward_loss"] is None) == (mesh_shape is not None)
    if mesh_shape is None:
        assert out["forward_loss"] == pytest.approx(out["losses"][0],
                                                    rel=1e-3)
    json.dumps(out)


@pytest.mark.parametrize("tp", [1, 2])
def test_serve_leg(tp):
    out = chip_smoke.leg_serve(TOY_SERVE, requests=6, seed=0, tp=tp,
                               **TOY_TRAFFIC)
    assert out["requests"] == 12
    assert out["tokens"] == 2 * sum(len(s) for s in out["streams"])
    assert out["routes"] == {"decode": "xla", "prefill_fwd": "xla"}


def test_prefill_rungs_leg():
    # a row of 256 and its half; fp32 on the CPU, where the narrower
    # row agrees with the widest to rounding
    cfg = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                             num_layers=2, max_position=256)
    out = chip_smoke.leg_prefill_rungs(cfg, seed=2, **TOY_TRAFFIC)
    assert out["widest"] == 256 and set(out["rungs"]) == {128, 256}
    for width, rung in out["rungs"].items():
        assert rung["route"] == "xla"
        assert rung["first"] == rung["first_widest"]
        assert max(rung["k"], rung["v"]) < 1e-5
    assert out["rungs"][256]["k"] == 0.0
    json.dumps(out)


def test_latent_leg():
    from apex_tpu.serving import DeepseekV2Config

    cfg = DeepseekV2Config(
        vocab_size=96, hidden_size=32, num_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, num_layers=2, first_k_dense_replace=1,
        intermediate_size=64, moe_intermediate_size=24, n_routed_experts=16,
        experts_held=(0, 2), top_k=3, n_group=8, topk_group=3,
        routed_scaling_factor=16.0, n_shared_experts=2, rope_original_max=32)
    out = chip_smoke.leg_latent(
        cfg, seed=3, page_size=8, row=32, doc_pages=4, max_new=3,
        walk=dict(rows=8, documents=2, doc_pages=4, own_pages=2))
    assert out["route"] == "xla" and out["shared_tokens"] == 32
    assert out["pool_width"] == 128
    walk = out["grouped_walk"]
    assert walk["blocks_walked"] == walk["blocks_fetched"] == 8
    assert walk["call_ms"] == "not measured"      # no chip here
    assert out["rel_l2"]["absorbed_vs_expanded"] < 1e-5
    json.dumps(out)


def test_state_space_leg():
    from apex_tpu.serving import GraniteHybridConfig

    cfg = GraniteHybridConfig(
        vocab_size=96, hidden_size=64, num_heads=8, num_kv_heads=2,
        layer_types=tuple("attention" if i == 5 else "mamba"
                          for i in range(10)),
        intermediate_size=128, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=16, attention_multiplier=0.125, mamba_chunk_size=8,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0)
    out = chip_smoke.leg_state_space(cfg, seed=4, page_size=8, row=32,
                                     max_new=3)
    assert out["route"] == "xla" and out["page_head_dim"] == 128
    assert max(out["rel_l2"].values()) < 1e-4
    json.dumps(out)


def test_warm_leg():
    out = chip_smoke.leg_warm(TOY_SERVE, spec_k=2, chunk_size=16, seed=1,
                              **TOY_TRAFFIC)
    assert set(out) == {"bf16", "int8"}
    assert all(leg["tokens"] > 0 for leg in out.values())
    # fp32 on the CPU: the decode and verify executables agree exactly
    assert all(leg["streams_repeated"] for leg in out.values())

