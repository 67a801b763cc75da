"""End-to-end Megatron GPT/BERT tests on the 8-device emulated mesh.

Mirrors the reference's canonical integration tests (SURVEY.md §4):
run_megatron_gpt_pipeline.py (GPT fwd+bwd under PP, loss parity vs
single-stage), run_bert_minimal_test.py, with TP sharding checked against a
tp=1 run of the same master weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.pipeline_parallel import (
    forward_backward_pipelining_without_interleaving,
)
from apex_tpu.transformer.testing import (
    BertConfig,
    BertModel,
    GPTConfig,
    GPTModel,
    make_gpt_stage_fns,
)

VOCAB = 32
SEQ = 8
B = 4


def _tokens(key, b=B):
    return jax.random.randint(key, (b, SEQ), 0, VOCAB)


def _serial_gpt_loss(cfg1, master, tokens, labels):
    """tp=1 reference run on the master weights (single device semantics
    inside a world-spanning shard_map so axis names resolve)."""
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(1, 1)
    model = GPTModel(cfg1)
    sharded = model.shard_master(master, 0)

    def run(p, t, l):
        return jnp.mean(model.apply(p, t, labels=l))

    out = shard_map(run, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
                    check_rep=False)(sharded, tokens, labels)
    parallel_state.destroy_model_parallel()
    return out


class TestGPTTensorParallel:
    @pytest.mark.slow  # 8-device TP4 parity (ISSUE 2 CI satellite)
    def test_tp4_matches_tp1(self):
        # reference run_layers_test/run_megatron_gpt: same master weights,
        # different tp -> identical loss
        cfg1 = GPTConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                         vocab_size=VOCAB, max_position_embeddings=SEQ,
                         tp_size=1)
        master = GPTModel(cfg1).init_master(jax.random.PRNGKey(0))
        tokens = _tokens(jax.random.PRNGKey(1))
        labels = _tokens(jax.random.PRNGKey(2))
        ref = _serial_gpt_loss(cfg1, master, tokens, labels)

        cfg4 = GPTConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                         vocab_size=VOCAB, max_position_embeddings=SEQ,
                         tp_size=4)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(4, 1)
        model = GPTModel(cfg4)
        shards = [model.shard_master(master, r) for r in range(4)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)

        def run(p, t, l):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            return jnp.mean(model.apply(p, t, labels=l))

        out = shard_map(run, mesh=mesh, in_specs=(P("tensor"), P(), P()),
                        out_specs=P(), check_rep=False)(stacked, tokens, labels)
        parallel_state.destroy_model_parallel()
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-5)

    def test_gpt_grads_flow(self):
        cfg = GPTConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                        vocab_size=VOCAB, max_position_embeddings=SEQ,
                        tp_size=2)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(2, 1)
        model = GPTModel(cfg)
        master = GPTModel(GPTConfig(**{**cfg.__dict__, "tp_size": 1})
                          ).init_master(jax.random.PRNGKey(0))
        shards = [model.shard_master(master, r) for r in range(2)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)
        tokens = _tokens(jax.random.PRNGKey(1))
        labels = _tokens(jax.random.PRNGKey(2))

        def loss(p, t, l):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            return jnp.mean(model.apply(p, t, labels=l))

        def run(p, t, l):
            return jax.value_and_grad(loss)(p, t, l)

        lv, grads = shard_map(run, mesh=mesh,
                              in_specs=(P("tensor"), P(), P()),
                              out_specs=(P(), P("tensor")),
                              check_rep=False)(stacked, tokens, labels)
        parallel_state.destroy_model_parallel()
        assert np.isfinite(float(lv))
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)
        assert max(float(jnp.abs(g).max()) for g in leaves) > 0


class TestGPTPipeline:
    def test_pp4_loss_matches_single_stage(self):
        # the reference's headline assertion (run_megatron_gpt_pipeline.py:78):
        # pipeline-parallel GPT loss == single-stage loss
        PP = 4
        N_MICRO = 4
        cfg = GPTConfig(num_layers=4, hidden_size=32, num_attention_heads=4,
                        vocab_size=VOCAB, max_position_embeddings=SEQ,
                        tp_size=1)
        master = GPTModel(cfg).init_master(jax.random.PRNGKey(0))
        tokens = _tokens(jax.random.PRNGKey(1), b=N_MICRO * 2)
        labels = _tokens(jax.random.PRNGKey(2), b=N_MICRO * 2)
        ref = _serial_gpt_loss(cfg, master, tokens, labels)

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, PP)
        stage_fn, loss_fn = make_gpt_stage_fns(cfg, PP)

        # stage s params: its layer slice + (embedding, head on all stages
        # for SPMD-uniform structure; only first/last use them)
        per_layer = cfg.num_layers // PP

        def stage_params(s):
            p = GPTModel(cfg, num_layers=per_layer).shard_master(
                {**master,
                 "transformer": {"layers": jax.tree_util.tree_map(
                     lambda a: a[s * per_layer:(s + 1) * per_layer],
                     master["transformer"]["layers"])}}, 0)
            return p

        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[stage_params(s) for s in range(PP)])
        microbatches = {
            "tokens": tokens.reshape(N_MICRO, 2, SEQ),
            "labels": labels.reshape(N_MICRO, 2, SEQ),
        }

        def run(p, mb):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            (loss,) = forward_backward_pipelining_without_interleaving(
                stage_fn, loss_fn, p, mb,
                n_microbatches=N_MICRO,
                tensor_shape=(2, SEQ, cfg.hidden_size),
                forward_only=True)
            return loss

        out = shard_map(run, mesh=mesh, in_specs=(P("pipeline"), P()),
                        out_specs=P(), check_rep=False)(stacked, microbatches)
        parallel_state.destroy_model_parallel()
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-5)

    def test_pp_training_decreases_loss(self):
        PP = 2
        N_MICRO = 4
        cfg = GPTConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                        vocab_size=VOCAB, max_position_embeddings=SEQ,
                        tp_size=1)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, PP)
        stage_fn, loss_fn = make_gpt_stage_fns(cfg, PP)
        per_layer = cfg.num_layers // PP
        master = GPTModel(cfg).init_master(jax.random.PRNGKey(0))

        def stage_params(s):
            return GPTModel(cfg, num_layers=per_layer).shard_master(
                {**master,
                 "transformer": {"layers": jax.tree_util.tree_map(
                     lambda a: a[s * per_layer:(s + 1) * per_layer],
                     master["transformer"]["layers"])}}, 0)

        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[stage_params(s) for s in range(PP)])
        tokens = _tokens(jax.random.PRNGKey(1), b=N_MICRO * 2)
        mb = {"tokens": tokens.reshape(N_MICRO, 2, SEQ),
              "labels": jnp.roll(tokens, -1, axis=-1).reshape(N_MICRO, 2, SEQ)}

        @jax.jit
        def train_step(p, mb):
            def run(p, mb):
                p_local = jax.tree_util.tree_map(lambda a: a[0], p)
                loss, grads = forward_backward_pipelining_without_interleaving(
                    stage_fn, loss_fn, p_local, mb,
                    n_microbatches=N_MICRO,
                    tensor_shape=(2, SEQ, cfg.hidden_size))
                # restore the leading stage axis so out_specs P("pipeline")
                # reassembles grads with the same shape as params
                grads = jax.tree_util.tree_map(lambda g: g[None], grads)
                return loss, grads
            return shard_map(run, mesh=mesh, in_specs=(P("pipeline"), P()),
                             out_specs=(P(), P("pipeline")),
                             check_rep=False)(p, mb)

        losses = []
        p = stacked
        for _ in range(8):
            loss, g = train_step(p, mb)
            p = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)
            losses.append(float(loss))
        parallel_state.destroy_model_parallel()
        assert losses[-1] < losses[0], losses


class TestBert:
    @pytest.mark.slow  # heaviest 8-device parity tier (ISSUE 6 wall-clock)
    def test_bert_packed_matches_padded(self):
        """Varlen packing (r7, ISSUE 5): two sequences packed into one
        row with segment ids + per-segment positions must produce the
        SAME per-token MLM losses as the padded two-row layout, on both
        the flash path (packed-QKV varlen route on chip, XLA fallback
        here) and the fused-softmax reference path (segment mask through
        the boolean-mask softmax)."""
        seq = 16
        kw = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
                  vocab_size=VOCAB, max_position_embeddings=seq,
                  tp_size=1, add_binary_head=False, num_tokentypes=0)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, 1)
        lens = [6, 10]
        toks = [jax.random.randint(jax.random.PRNGKey(i + 1), (n,), 0,
                                   VOCAB) for i, n in enumerate(lens)]
        labs = [jax.random.randint(jax.random.PRNGKey(i + 10), (n,), 0,
                                   VOCAB) for i, n in enumerate(lens)]
        # padded: one row per sequence + key-padding mask
        tok_p = jnp.zeros((2, seq), jnp.int32)
        lab_p = jnp.zeros((2, seq), jnp.int32)
        msk_p = jnp.zeros((2, seq), jnp.int32)
        for i, n in enumerate(lens):
            tok_p = tok_p.at[i, :n].set(toks[i])
            lab_p = lab_p.at[i, :n].set(labs[i])
            msk_p = msk_p.at[i, :n].set(1)
        # packed: both sequences in ONE row, positions restarting
        tok_k = jnp.concatenate(toks)[None]
        lab_k = jnp.concatenate(labs)[None]
        seg_k = jnp.concatenate([jnp.full((n,), i, jnp.int32)
                                 for i, n in enumerate(lens)])[None]
        pos_k = jnp.concatenate([jnp.arange(n) for n in lens])[None]

        def run(model, packed):
            def f(p, *args):
                if packed:
                    losses, _ = model.apply(p, tok_k, lm_labels=lab_k,
                                            segment_ids=seg_k,
                                            position_ids=pos_k)
                else:
                    losses, _ = model.apply(p, tok_p,
                                            attention_mask=msk_p,
                                            lm_labels=lab_p)
                return losses
            return shard_map(f, mesh=mesh, in_specs=(P(),),
                             out_specs=P(), check_rep=False)(params)

        for flash in (True, False):
            model = BertModel(BertConfig(use_flash_attention=flash, **kw))
            master = model.init_master(jax.random.PRNGKey(0))
            params = model.shard_master(master, 0)
            l_pad = run(model, packed=False)
            l_pack = run(model, packed=True)
            # real-token losses line up: packed row = concat of the
            # padded rows' real prefixes
            ref = jnp.concatenate([l_pad[i, :n]
                                   for i, n in enumerate(lens)])
            np.testing.assert_allclose(
                np.asarray(l_pack[0]), np.asarray(ref), rtol=2e-5,
                atol=2e-5, err_msg=f"flash={flash}")
        parallel_state.destroy_model_parallel()

    # slow since PR 22: pays for test_tpu_lowering / test_chip_smoke in tier-1
    def test_bert_forward_and_loss(self):
        cfg = BertConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                         vocab_size=VOCAB, max_position_embeddings=SEQ,
                         tp_size=2)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(2, 1)
        model = BertModel(cfg)
        cfg1 = BertConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                          vocab_size=VOCAB, max_position_embeddings=SEQ,
                          tp_size=1)
        master = BertModel(cfg1).init_master(jax.random.PRNGKey(0))
        shards = [model.shard_master(master, r) for r in range(2)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)
        tokens = _tokens(jax.random.PRNGKey(1))
        mask = jnp.ones((B, SEQ), jnp.int32)
        labels = _tokens(jax.random.PRNGKey(2))

        def run(p, t, m, l):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            losses, binary = model.apply(p, t, attention_mask=m, lm_labels=l)
            return jnp.mean(losses), binary

        loss, binary = shard_map(
            run, mesh=mesh, in_specs=(P("tensor"), P(), P(), P()),
            out_specs=(P(), P()), check_rep=False)(stacked, tokens, mask, labels)
        parallel_state.destroy_model_parallel()
        assert np.isfinite(float(loss))
        assert binary.shape == (B, 2)

    def test_bert_flash_matches_softmax_path(self):
        """BERT's key-padding mask through the flash path (segment ids
        with all-ones query ids — the FMHA varlen role, r5) must match
        the fused-softmax path: key-side-only masking semantics, pad
        query rows included."""
        kw = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
                  vocab_size=VOCAB, max_position_embeddings=SEQ,
                  tp_size=1, add_binary_head=False)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, 1)
        m_soft = BertModel(BertConfig(**kw))
        m_flash = BertModel(BertConfig(use_flash_attention=True, **kw))
        master = m_soft.init_master(jax.random.PRNGKey(0))
        params = m_soft.shard_master(master, 0)
        tokens = _tokens(jax.random.PRNGKey(1))
        # real padding: last third of every sequence masked
        mask = jnp.concatenate(
            [jnp.ones((B, SEQ - SEQ // 3), jnp.int32),
             jnp.zeros((B, SEQ // 3), jnp.int32)], axis=1)
        labels = _tokens(jax.random.PRNGKey(2))

        def run(model):
            def f(p, t, m, l):
                losses, _ = model.apply(p, t, attention_mask=m,
                                        lm_labels=l)
                return losses
            return shard_map(
                f, mesh=mesh, in_specs=(P(), P(), P(), P()),
                out_specs=P(), check_rep=False)(params, tokens, mask,
                                                labels)

        l_soft = run(m_soft)
        l_flash = run(m_flash)
        parallel_state.destroy_model_parallel()
        np.testing.assert_allclose(np.asarray(l_flash),
                                   np.asarray(l_soft),
                                   rtol=2e-3, atol=2e-3)

    def test_bert_tp_matches_tp1(self):
        cfg1 = BertConfig(num_layers=1, hidden_size=32, num_attention_heads=4,
                          vocab_size=VOCAB, max_position_embeddings=SEQ,
                          tp_size=1, add_binary_head=False)
        master = BertModel(cfg1).init_master(jax.random.PRNGKey(0))
        tokens = _tokens(jax.random.PRNGKey(1))
        mask = jnp.ones((B, SEQ), jnp.int32)
        labels = _tokens(jax.random.PRNGKey(2))

        def loss_for_tp(tp):
            cfg = BertConfig(num_layers=1, hidden_size=32,
                             num_attention_heads=4, vocab_size=VOCAB,
                             max_position_embeddings=SEQ, tp_size=tp,
                             add_binary_head=False)
            parallel_state.destroy_model_parallel()
            mesh = parallel_state.initialize_model_parallel(tp, 1)
            model = BertModel(cfg)
            shards = [model.shard_master(master, r) for r in range(tp)]
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)

            def run(p, t, m, l):
                p = jax.tree_util.tree_map(lambda a: a[0], p)
                losses, _ = model.apply(p, t, attention_mask=m, lm_labels=l)
                return jnp.mean(losses)

            out = shard_map(run, mesh=mesh,
                            in_specs=(P("tensor"), P(), P(), P()),
                            out_specs=P(), check_rep=False)(
                stacked, tokens, mask, labels)
            parallel_state.destroy_model_parallel()
            return out

        np.testing.assert_allclose(loss_for_tp(4), loss_for_tp(1),
                                   rtol=2e-4, atol=1e-5)


class TestFlashAndRemat:
    """The TPU-first GPTConfig extensions (use_flash_attention, remat) must
    not change the math: same master weights -> same loss as the
    reference-shaped softmax path."""

    def _loss(self, cfg, master, tokens, labels):
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, 1)
        model = GPTModel(cfg)
        p = model.shard_master(master, 0)

        def run(p, t, l):
            return jnp.mean(model.apply(p, t, labels=l))

        out = shard_map(run, mesh=mesh, in_specs=(P(), P(), P()),
                        out_specs=P(), check_rep=False)(p, tokens, labels)
        parallel_state.destroy_model_parallel()
        return float(out)

    def test_flash_and_remat_match_reference_path(self):
        kw = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
                  vocab_size=VOCAB, max_position_embeddings=SEQ, tp_size=1)
        master = GPTModel(GPTConfig(**kw)).init_master(jax.random.PRNGKey(0))
        tokens = _tokens(jax.random.PRNGKey(1))
        labels = _tokens(jax.random.PRNGKey(2))
        base = self._loss(GPTConfig(**kw), master, tokens, labels)
        flash = self._loss(GPTConfig(**kw, use_flash_attention=True),
                           master, tokens, labels)
        remat = self._loss(GPTConfig(**kw, use_flash_attention=True,
                                     remat=True), master, tokens, labels)
        np.testing.assert_allclose(flash, base, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(remat, base, rtol=2e-5, atol=2e-6)

    def test_causal_model_keeps_causality_with_padding_mask(self):
        """A causal model handed an ADDITIONAL [b,1,1,s] padding mask
        must stay causal on the flash path (r5 review finding: the
        key-padding flash branch once dropped the causal mask)."""
        from apex_tpu.transformer.testing.standalone_gpt import (
            ParallelAttention)

        cfg = GPTConfig(num_layers=1, hidden_size=32,
                        num_attention_heads=4, vocab_size=VOCAB,
                        max_position_embeddings=SEQ, tp_size=1)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, 1)
        attn_soft = ParallelAttention(cfg)
        attn_flash = ParallelAttention(
            GPTConfig(num_layers=1, hidden_size=32,
                      num_attention_heads=4, vocab_size=VOCAB,
                      max_position_embeddings=SEQ, tp_size=1,
                      use_flash_attention=True))
        params = attn_soft.shard_master(
            attn_soft.init_master(jax.random.PRNGKey(0)), 0)
        h = jax.random.normal(jax.random.PRNGKey(1), (B, SEQ, 32))
        pad = jnp.concatenate(
            [jnp.zeros((B, SEQ - 2), bool), jnp.ones((B, 2), bool)],
            axis=1)[:, None, None, :]  # True = masked key

        def run(attn):
            return shard_map(
                lambda p, h: attn.apply(p, h, attention_mask=pad),
                mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                check_rep=False)(params, h)

        o_soft = run(attn_soft)
        o_flash = run(attn_flash)
        parallel_state.destroy_model_parallel()
        np.testing.assert_allclose(np.asarray(o_flash),
                                   np.asarray(o_soft),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.slow  # remat grad parity (interpret-mode kernels) (ISSUE 2 CI satellite)
    def test_remat_grads_match(self):
        kw = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
                  vocab_size=VOCAB, max_position_embeddings=SEQ, tp_size=1)
        tokens = _tokens(jax.random.PRNGKey(1))
        labels = _tokens(jax.random.PRNGKey(2))

        def grads_for(cfg):
            parallel_state.destroy_model_parallel()
            mesh = parallel_state.initialize_model_parallel(1, 1)
            model = GPTModel(cfg)
            master = GPTModel(GPTConfig(**kw)).init_master(
                jax.random.PRNGKey(0))
            p = model.shard_master(master, 0)

            def loss(p):
                def run(p, t, l):
                    return jnp.mean(model.apply(p, t, labels=l))
                return shard_map(run, mesh=mesh, in_specs=(P(), P(), P()),
                                 out_specs=P(), check_rep=False)(
                    p, tokens, labels)

            g = jax.grad(loss)(p)
            parallel_state.destroy_model_parallel()
            return g

        g0 = grads_for(GPTConfig(**kw))
        g1 = grads_for(GPTConfig(**kw, use_flash_attention=True, remat=True))
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestDropout:
    """The reference RNG-tracker property (run_random_test.py +
    random.py:193-221): dropout on TP-*replicated* activations must be
    identical across ranks, dropout on TP-*sharded* activations must
    differ — and the model must stay TP-consistent with both on."""

    def test_mask_streams_tp_property(self):
        from apex_tpu.transformer.tensor_parallel.random import (
            dropout, model_parallel_dropout_key)

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(4, 1)
        base = jax.random.PRNGKey(3)
        x = jnp.ones((64, 16))

        def run(_):
            rep = dropout(x, 0.5, base)                           # replicated
            shd = dropout(x, 0.5, model_parallel_dropout_key(base))  # sharded
            return rep[None], shd[None]

        rep, shd = shard_map(
            run, mesh=mesh, in_specs=(P("tensor"),),
            out_specs=(P("tensor"), P("tensor")), check_rep=False)(
            jnp.zeros((4, 1)))
        parallel_state.destroy_model_parallel()
        for r in range(1, 4):
            np.testing.assert_array_equal(np.asarray(rep[0]),
                                          np.asarray(rep[r]))
        assert any(not np.array_equal(np.asarray(shd[0]), np.asarray(shd[r]))
                   for r in range(1, 4))

    def _dropout_cfg(self, tp):
        return GPTConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                         vocab_size=VOCAB, max_position_embeddings=SEQ,
                         tp_size=tp, attention_dropout=0.3,
                         hidden_dropout=0.25)

    @pytest.mark.slow  # 8-device dropout statistics (ISSUE 2 CI satellite)
    def test_dropout_active_and_deterministic(self):
        cfg = self._dropout_cfg(1)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, 1)
        model = GPTModel(cfg)
        params = model.shard_master(model.init_master(jax.random.PRNGKey(0)), 0)
        tokens, labels = _tokens(jax.random.PRNGKey(1)), _tokens(jax.random.PRNGKey(2))

        def loss(key):
            def run(p, t, l):
                return jnp.mean(model.apply(p, t, labels=l, dropout_key=key))
            return float(shard_map(run, mesh=mesh, in_specs=(P(), P(), P()),
                                   out_specs=P(), check_rep=False)(
                params, tokens, labels))

        def loss_eval():
            def run(p, t, l):
                return jnp.mean(model.apply(p, t, labels=l))
            return float(shard_map(run, mesh=mesh, in_specs=(P(), P(), P()),
                                   out_specs=P(), check_rep=False)(
                params, tokens, labels))

        la = loss(jax.random.PRNGKey(7))
        lb = loss(jax.random.PRNGKey(7))
        lc = loss(jax.random.PRNGKey(8))
        le = loss_eval()
        parallel_state.destroy_model_parallel()
        assert la == lb                  # same key -> bitwise same
        assert la != lc                  # different key -> different masks
        assert la != le                  # dropout actually does something
        assert np.isfinite(la) and np.isfinite(le)

    def test_tp2_stays_consistent_with_dropout(self):
        """With attention (sharded-stream) AND hidden (replicated-stream)
        dropout on, every TP rank must compute the SAME transformer
        output — the property the whole tracker design exists for.  It
        fails if hidden dropout ever uses a per-rank stream."""
        cfg = self._dropout_cfg(2)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(2, 1)
        model = GPTModel(cfg)
        master = GPTModel(self._dropout_cfg(1)).init_master(
            jax.random.PRNGKey(0))
        shards = [model.shard_master(master, r) for r in range(2)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)
        tokens = _tokens(jax.random.PRNGKey(1))
        key = jax.random.PRNGKey(11)

        def run(p, t):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            h = model.embed(p, t)
            h, _aux = model.transformer.apply(p["transformer"], h,
                                              dropout_key=key)
            return h[None]

        hs = shard_map(run, mesh=mesh, in_specs=(P("tensor"), P()),
                       out_specs=P("tensor"), check_rep=False)(
            stacked, tokens)
        parallel_state.destroy_model_parallel()
        np.testing.assert_allclose(np.asarray(hs[0]), np.asarray(hs[1]),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.slow  # 8-device BERT dropout statistics (ISSUE 2 CI satellite)
    def test_bert_dropout_active_and_deterministic(self):
        cfg = BertConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                         vocab_size=VOCAB, max_position_embeddings=SEQ,
                         tp_size=1, attention_dropout=0.3,
                         hidden_dropout=0.25)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, 1)
        model = BertModel(cfg)
        params = model.shard_master(
            model.init_master(jax.random.PRNGKey(0)), 0)
        tokens = _tokens(jax.random.PRNGKey(1))
        labels = _tokens(jax.random.PRNGKey(2))
        amask = jnp.ones_like(tokens)

        def loss(key):
            def run(p, t, l):
                losses, _ = model.apply(p, t, attention_mask=amask,
                                        lm_labels=l, dropout_key=key)
                return jnp.mean(losses)
            return float(shard_map(run, mesh=mesh, in_specs=(P(), P(), P()),
                                   out_specs=P(), check_rep=False)(
                params, tokens, labels))

        la = loss(jax.random.PRNGKey(3))
        lb = loss(jax.random.PRNGKey(3))
        lc = loss(jax.random.PRNGKey(4))
        parallel_state.destroy_model_parallel()
        assert la == lb and la != lc and np.isfinite(la)

    @pytest.mark.slow  # 8-device in-kernel dropout (ISSUE 2 CI satellite)
    def test_flash_path_dropout_in_kernel(self):
        """use_flash_attention + attention_dropout uses the in-kernel
        dropout (no S×S probs): deterministic per key, active, and the
        TP2 consistency property still holds."""
        cfg = GPTConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                        vocab_size=VOCAB, max_position_embeddings=SEQ,
                        tp_size=1, attention_dropout=0.3,
                        hidden_dropout=0.0, use_flash_attention=True)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, 1)
        model = GPTModel(cfg)
        params = model.shard_master(
            model.init_master(jax.random.PRNGKey(0)), 0)
        tokens = _tokens(jax.random.PRNGKey(1))
        labels = _tokens(jax.random.PRNGKey(2))

        def loss(key):
            def run(p, t, l):
                return jnp.mean(model.apply(p, t, labels=l,
                                            dropout_key=key))
            return float(shard_map(run, mesh=mesh,
                                   in_specs=(P(), P(), P()),
                                   out_specs=P(), check_rep=False)(
                params, tokens, labels))

        def loss_eval():
            def run(p, t, l):
                return jnp.mean(model.apply(p, t, labels=l))
            return float(shard_map(run, mesh=mesh,
                                   in_specs=(P(), P(), P()),
                                   out_specs=P(), check_rep=False)(
                params, tokens, labels))

        la = loss(jax.random.PRNGKey(7))
        lb = loss(jax.random.PRNGKey(7))
        lc = loss(jax.random.PRNGKey(8))
        le = loss_eval()
        parallel_state.destroy_model_parallel()
        assert la == lb and la != lc and la != le
        assert np.isfinite(la)


class TestMoEGPT:
    """GPTConfig(num_experts>0): every layer's MLP is Switch-routed
    (TPU-first extension; experts replicated across TP)."""

    def _cfg(self, tp):
        return GPTConfig(num_layers=2, hidden_size=32, num_attention_heads=4,
                         vocab_size=VOCAB, max_position_embeddings=SEQ,
                         tp_size=tp, num_experts=4,
                         moe_capacity_factor=8.0)

    def test_moe_gpt_trains(self):
        from apex_tpu import optimizers

        cfg = self._cfg(1)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(1, 1)
        model = GPTModel(cfg)
        params = model.shard_master(
            model.init_master(jax.random.PRNGKey(0)), 0)
        opt = optimizers.FusedAdam(lr=3e-3)
        opt_state = opt.init(params)
        tokens = _tokens(jax.random.PRNGKey(1))
        labels = _tokens(jax.random.PRNGKey(2))

        # jax 0.4.37 compat: under check_rep=False, shard_map AD turns
        # forward residuals into extra outputs with inferred specs, and
        # the MoE aux-loss SCALAR residual has no rank to carry them —
        # value_and_grad over the bare shard_map dies with _SpecError.
        # jax.checkpoint over the shard_map keeps residuals internal
        # (the backward re-runs the forward inside), same math.
        inner = shard_map(
            lambda p, t, l: jnp.mean(model.apply(p, t, labels=l)),
            mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_rep=False)

        @jax.jit
        def step(p, o):
            def lossf(p):
                return jax.checkpoint(inner)(p, tokens, labels)

            loss, g = jax.value_and_grad(lossf)(p)
            p, o = opt.step(g, o, p)
            return p, o, loss, g

        first = None
        for _ in range(25):
            params, opt_state, loss, g = step(params, opt_state)
            if first is None:
                first = float(loss)
                # gradients flow into gate and experts of every layer
                ml = g["transformer"]["layers"]["mlp"]
                assert float(jnp.abs(ml["gate"]["weight"]).max()) > 0
                assert float(jnp.abs(ml["experts"]["w1"]).max()) > 0
        parallel_state.destroy_model_parallel()
        assert np.isfinite(float(loss)) and float(loss) < first

    def test_moe_gpt_tp2_matches_tp1(self):
        """Experts replicated across TP: tp=2 must equal tp=1 exactly
        (gate runs on the TP-replicated hidden, routing agrees)."""
        master = GPTModel(self._cfg(1)).init_master(jax.random.PRNGKey(0))
        tokens = _tokens(jax.random.PRNGKey(1))
        labels = _tokens(jax.random.PRNGKey(2))
        ref = _serial_gpt_loss(self._cfg(1), master, tokens, labels)

        cfg2 = self._cfg(2)
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(2, 1)
        model = GPTModel(cfg2)
        shards = [model.shard_master(master, r) for r in range(2)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)

        def run(p, t, l):
            p = jax.tree_util.tree_map(lambda a: a[0], p)
            return jnp.mean(model.apply(p, t, labels=l))

        out = shard_map(run, mesh=mesh, in_specs=(P("tensor"), P(), P()),
                        out_specs=P(), check_rep=False)(
            stacked, tokens, labels)
        parallel_state.destroy_model_parallel()
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-5)
