"""Cross-lower every Pallas route auto-routing can pick on a TPU.

The CPU tiers run each kernel in interpret mode, which has no block
rules, and route every shape gate to XLA.  Pallas' TPU lowering can be
driven from the CPU all the same: trace with ``ShapeDtypeStruct``s and
lower for ``lowering_platforms=("tpu",)`` with ``jax.default_backend``
patched to ``"tpu"`` (the routing tests' idiom).  That runs Mosaic's
block-shape and layout checks at the REAL shapes without a chip — the
check that a unit block on a second-minor axis, the bug that kept the
serving engine from starting on hardware, cannot pass.

Lowering is not compiling: what Mosaic makes of VMEM limits and of
layout changes inside a kernel is only found by the compiler on the
chip (``chip_smoke.py``).
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.ops import (flash_attention, flash_attention_qkv,
                          flash_attention_qkv_route, flash_attention_route,
                          flash_decode, flash_decode_latent,
                          flash_decode_latent_route, flash_decode_route,
                          latent_walk_tiles, layer_norm, routing_override,
                          ssm_decode_route, ssm_decode_update)

SDS = jax.ShapeDtypeStruct
BF16 = jnp.bfloat16


@pytest.fixture(autouse=True)
def _as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _tpu_text(fn, *structs) -> str:
    """``fn`` lowered for the TPU platform, as text."""
    lowered = jax.jit(fn).trace(*structs).lower(lowering_platforms=("tpu",))
    return lowered.as_text()


def _mosaic_calls(fn, *structs) -> int:
    """Lower ``fn`` for the TPU platform and count its Mosaic kernels."""
    return _tpu_text(fn, *structs).count("tpu_custom_call")


def _grad_of(fn):
    def run(x, *rest):
        return jax.grad(
            lambda x: fn(x, *rest).astype(jnp.float32).sum())(x)
    return run


# -- paged decode: the serving engine's decode / verify / chunk steps -------

@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("q_len", [1, 5, 128])
@pytest.mark.parametrize("heads", [16, 8, 4])
def test_paged_decode_lowers(heads, q_len, quantized):
    # the 1.3B-geometry pool (page 64, d=128) at tp 1, 2 and 4; q_len is
    # plain decode, the spec.k + 1 verify window, and one prefill chunk
    b, n_pages, page, d, p_max = (1 if q_len == 128 else 8), 61, 64, 128, 16
    q = SDS((b, heads, q_len, d), BF16)
    pool = SDS((n_pages, page, heads, d), jnp.int8 if quantized else BF16)
    args = [q, pool, pool, SDS((b, p_max), jnp.int32), SDS((b,), jnp.int32)]
    assert flash_decode_route(q, pool) == "decode"
    if quantized:
        scale = SDS((n_pages, page, heads), jnp.float32)
        fn = lambda q, k, v, pt, kl, ks, vs: flash_decode(
            q, k, v, pt, kl, k_scale=ks, v_scale=vs)
        args += [scale, scale]
    else:
        fn = flash_decode
    assert _mosaic_calls(fn, *args) == 1


@pytest.mark.parametrize("layer", [0, 23])
@pytest.mark.parametrize("pool_dtype", [BF16, jnp.int8, jnp.float8_e4m3fn],
                         ids=["bf16", "int8", "fp8"])
def test_paged_decode_lowers_on_the_whole_pool(pool_dtype, layer):
    # the serving configuration's decode step as the engine runs it:
    # the whole 24-layer pool is the operand and the layer rides in the
    # index map (benchmark/configs/gpt1p3b-serve.json: 600 pages of 64
    # tokens, 32 rows of at most 28 pages)
    b, heads, d, p_max = 32, 16, 128, 28
    q = SDS((b, heads, 1, d), BF16)
    pool = SDS((24, 600, 64, heads, d), pool_dtype)
    args = [q, pool, pool, SDS((b, p_max), jnp.int32), SDS((b,), jnp.int32)]
    assert flash_decode_route(q, pool) == "decode"
    names = []
    if pool_dtype != BF16:
        names = ["k_scale", "v_scale"]
        args += [SDS(pool.shape[:-1], jnp.float32)] * 2

    def fn(q, k, v, pt, kl, *scales):
        return flash_decode(q, k, v, pt, kl, layer=layer,
                            **dict(zip(names, scales)))

    calls = [line for line in _tpu_text(fn, *args).splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    # the kernel's K and V operands are the pool, not a slice of it
    call = calls[0]
    whole = "x".join(map(str, pool.shape))
    assert call.count(f"tensor<{whole}x") == 2, call[-600:]


# the shapes the cells run (benchmark/configs/gpt1p3b-serve.json,
# trinity-large-serve-ep8-l5.json): batch, query heads, q_len, the pool,
# p_max, window; a window pool's table comes with kv_start
CELL_GEOMETRIES = {
    "gpt_decode": (32, 16, 1, (24, 600, 64, 16, 128), 28, None),
    "trinity_decode_full": (64, 48, 1, (1, 6144, 64, 8, 128), 264, None),
    "trinity_decode_window": (64, 48, 1, (4, 3072, 64, 8, 128), 97, 4096),
    "trinity_chunk_full": (1, 48, 2048, (1, 6144, 64, 8, 128), 264, None),
    "trinity_chunk_window": (1, 48, 2048, (4, 3072, 64, 8, 128), 97, 4096),
    # granite-4.0-h-micro-serve.json: 32 query heads over 8 K/V heads
    # of 64, stored 128 wide
    "granite_decode": (64, 32, 1, (4, 1600, 64, 8, 128), 32, None),
    "granite_chunk": (1, 32, 1024, (4, 1600, 64, 8, 128), 32, None),
}


@pytest.mark.parametrize("name", list(CELL_GEOMETRIES))
def test_paged_decode_lowers_at_the_cells_geometries(name):
    # all heads at once over blocks of 4 and 8 pages, and the chunk's
    # tiles of 64 positions x 6 heads over blocks of 16
    b, hq, q_len, pool_shape, p_max, window = CELL_GEOMETRIES[name]
    q = SDS((b, hq, q_len, 128), BF16)
    pool = SDS(pool_shape, BF16)
    args = [q, pool, pool, SDS((b, p_max), jnp.int32), SDS((b,), jnp.int32)]
    if window is not None:
        args.append(SDS((b,), jnp.int32))
    assert flash_decode_route(q, pool) == "decode"

    def fn(q, k, v, pt, kl, *start):
        return flash_decode(q, k, v, pt, kl, layer=pool_shape[0] - 1,
                            window=window,
                            kv_start=start[0] if start else None)

    calls = [line for line in _tpu_text(fn, *args).splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    kernel = "flash_decode_window" if window is not None else "flash_decode"
    assert f'kernel_name = "{kernel}"' in calls[0]
    whole = "x".join(map(str, pool_shape))
    assert calls[0].count(f"tensor<{whole}x") == 2, calls[0][-600:]


# the latent cell (benchmark/configs/deepseek-v2-serve-ep8-l6.json): 128
# heads over one vector of 576 numbers, stored 640 wide; a decode step
# (the grouped walk of ISSUE 36, its tiles made by the call or handed in
# as the decoder hands them, once for all layers), the chunk, the
# smoke's chunk
@pytest.mark.parametrize("b, q_len, tiles", [
    (64, 1, False), (64, 1, True), (1, 512, False), (2, 8, False)])
def test_latent_decode_lowers_at_the_cells_geometry(b, q_len, tiles):
    q = SDS((b, q_len, 128, 640), BF16)
    pool = SDS((6, 4608, 64, 640), BF16)
    assert flash_decode_latent_route(q, pool) == "decode"

    def fn(q, pool, pt, kl, start):
        made = latent_walk_tiles(pt, kl, q_len=q_len, heads=128,
                                 page_size=64) if tiles else None
        return flash_decode_latent(q, pool, pt, kl, v_dim=512,
                                   scale=0.114721, layer=5, q_start=start,
                                   tiles=made)

    calls = [line for line in _tpu_text(
        fn, q, pool, SDS((b, 280), jnp.int32), SDS((b,), jnp.int32),
        SDS((b,), jnp.int32)).splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert 'kernel_name = "flash_decode_latent"' in calls[0]
    # ONE operand is the pool, whole: key and value at once
    assert calls[0].count("tensor<6x4608x64x640x") == 1, calls[0][-600:]


# the state-space cell (benchmark/configs/granite-4.0-h-micro-serve.json):
# 64 rows, each advancing its slot of the 36-layer pool in place
@pytest.mark.parametrize("layer", [0, 35])
def test_state_update_lowers_at_the_cells_geometry(layer):
    heads, lanes, n, ch = 64, 4096, 128, 4096 + 256
    ssm = SDS((36, 66, n, lanes), jnp.float32)
    conv = SDS((36, 66, 1, 3 * ch), BF16)
    assert ssm_decode_route(ssm) == "decode"
    # half a lane tile of state, or lanes that are not whole tiles: XLA
    assert ssm_decode_route(SDS((36, 66, 64, lanes), jnp.float32)) == "xla"
    assert ssm_decode_route(SDS((36, 66, n, 4000), jnp.float32)) == "xla"

    def fn(ssm, conv, slots, xbc, dt, conv_w, conv_b, dt_bias, a_log, d):
        return ssm_decode_update(
            ssm, conv, slots, xbc, dt, layer=layer, conv_w=conv_w,
            conv_b=conv_b, dt_bias=dt_bias, a_log=a_log, d_skip=d,
            heads=heads)

    head = SDS((heads,), BF16)
    calls = [line for line in _tpu_text(
        fn, ssm, conv, SDS((64,), jnp.int32), SDS((64, ch), BF16),
        SDS((64, heads), BF16), SDS((4, ch), BF16), SDS((ch,), BF16),
        head, head, head).splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert 'kernel_name = "ssm_decode_update"' in calls[0]
    # both pools are operands, whole, and come back in place
    assert calls[0].count("tensor<36x66x128x4096xf32>") == 2, calls[0][-600:]
    assert calls[0].count("tensor<36x66x1x13056xbf16>") == 2
    assert "output_operand_aliases" in calls[0]


def test_a_prefill_row_of_heads_of_64_lowers_on_the_varlen_route():
    # 32 query heads over 8 K/V heads of 64, a row of 1,024 and its half
    for width in (512, 1024):
        q, k = SDS((32, width, 64), BF16), SDS((8, width, 64), BF16)
        assert flash_attention_route(q, k, segment_ids=True)["fwd"] \
            == "varlen"
        fn = lambda q, k, v, seg: flash_attention(
            q, k, v, causal=True, segment_ids=seg, scale=1 / 64)
        assert _mosaic_calls(
            fn, SDS((1, 32, width, 64), BF16), SDS((1, 8, width, 64), BF16),
            SDS((1, 8, width, 64), BF16), SDS((1, width), jnp.int32)) == 1


# -- generic flash attention: block-skip routes with more than one block ----

def _seg_attention(q, k, v, seg, **kw):
    return flash_attention(q, k, v, causal=True, segment_ids=seg, **kw)


def test_segment_backward_auto_route_lowers():
    # any call with segments takes grid_skip backward by default; the
    # prefill shape of the serving model (s=1024, d=128) also takes the
    # varlen forward
    q = SDS((16, 1024, 128), BF16)
    seg = SDS((16, 1024), jnp.int32)
    kw = dict(block_q=256, block_k=256)
    assert flash_attention_route(q, segment_ids=True, **kw) == {
        "fwd": "varlen", "bwd": "grid_skip"}

    def loss(q, k, v, seg):
        return jax.grad(lambda q: _seg_attention(
            q, k, v, seg, **kw).astype(jnp.float32).sum())(q)

    assert _mosaic_calls(loss, q, q, q, seg) == 2


def test_stream_skip_forward_auto_route_lowers():
    # whole-sequence q/k/v no longer fit VMEM at s=8192: the streaming
    # forward reads the skip index one grid row at a time
    q = SDS((4, 8192, 128), BF16)
    seg = SDS((4, 8192), jnp.int32)
    assert flash_attention_route(q, segment_ids=True)["fwd"] == "stream_skip"
    assert _mosaic_calls(_seg_attention, q, q, q, seg) == 1


@pytest.mark.parametrize("fwd,bwd", [("stream_skip", "grid_skip"),
                                     ("varlen", "grid"),
                                     ("tiles", "tiles")])
def test_forced_generic_routes_lower(fwd, bwd):
    q = SDS((8, 1024, 128), BF16)
    seg = SDS((8, 1024), jnp.int32)
    kw = dict(block_q=128, block_k=128)

    def loss(q, k, v, seg):
        with routing_override(fwd=fwd, bwd=bwd):
            return jax.grad(lambda q: _seg_attention(
                q, k, v, seg, **kw).astype(jnp.float32).sum())(q)

    assert _mosaic_calls(loss, q, q, q, seg) == 2


def test_broadcast_segments_lower():
    # a [1, s] segment row shared by every batch-head (the serving
    # prefill's form) selects row 0 of the skip table
    q = SDS((16, 1024, 128), BF16)
    seg = SDS((1, 1024), jnp.int32)
    with routing_override(fwd="stream_skip"):
        assert _mosaic_calls(functools.partial(
            _seg_attention, block_q=256, block_k=256), q, q, q, seg) == 1


# -- packed-QKV: the training models' attention -----------------------------

_QKV_SHAPES = {
    # name: (batch, seq, heads, head_dim, block, causal)
    "gpt1p3b": (4, 2048, 16, 128, 256, True),
    "gpt1p3b_tp2": (4, 2048, 8, 128, 256, True),
    "gpt1p3b_tp4": (4, 2048, 4, 128, 256, True),
    "gpt350m": (8, 1024, 16, 64, 512, True),
    "bert_large": (8, 512, 16, 64, 512, False),
}


@pytest.mark.parametrize("segments", [False, True], ids=["dense", "varlen"])
@pytest.mark.parametrize("name", list(_QKV_SHAPES))
def test_packed_qkv_lowers(name, segments):
    b, s, heads, hn, block, causal = _QKV_SHAPES[name]
    assert flash_attention_qkv_route(
        b, s, heads, hn, block=block, causal=causal,
        has_segments=segments) == ("packed_varlen" if segments else "packed")
    qkv = SDS((b, s, heads * 3 * hn), BF16)

    def attn(qkv, *seg):
        return flash_attention_qkv(qkv, heads, causal=causal, block=block,
                                   segment_ids=seg[0] if seg else None)

    structs = (qkv,) + ((SDS((b, s), jnp.int32),) if segments else ())
    assert _mosaic_calls(_grad_of(attn), *structs) == 2


# -- LayerNorm and the flat Adam kernel -------------------------------------

def test_layer_norm_lowers():
    x = SDS((4 * 2048, 2048), BF16)
    w = SDS((2048,), jnp.float32)

    def loss(x, w, b):
        return jax.grad(lambda x, w, b: layer_norm(
            x, w, b).astype(jnp.float32).sum(), (0, 1, 2))(x, w, b)

    assert _mosaic_calls(loss, x, w, w) == 2


def test_flat_fused_adam_lowers():
    from apex_tpu.optimizers.flat import FlatAdamState, FlatFusedAdam

    n = 1 << 20
    flat = SDS((n,), jnp.float32)
    state = FlatAdamState(step=SDS((), jnp.int32), exp_avg=flat,
                          exp_avg_sq=flat)
    assert _mosaic_calls(FlatFusedAdam().step, flat, state, flat) == 1

