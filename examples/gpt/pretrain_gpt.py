"""Megatron-style GPT pretraining CLI on TPU meshes.

The user-facing counterpart of the reference's canonical GPT loop
(reference tests/L0/run_transformer/run_megatron_gpt_pipeline.py, itself
modeled on Megatron-LM's pretrain_gpt.py): build a GPT from the Megatron
argument surface (``apex_tpu.transformer.testing.arguments`` — the
argparse clone of reference testing/arguments.py:23-806), train with
data/tensor parallelism on a device mesh, checkpoint and resume.

Runs unchanged on one real TPU chip or an emulated CPU mesh:

    # 350M-class single chip
    python pretrain_gpt.py --num-layers 24 --hidden-size 1024 \\
        --num-attention-heads 16 --seq-length 1024 --micro-batch-size 8

    # emulated 8-way (2-way tensor x 4-way data) on CPU
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
    python pretrain_gpt.py --tensor-model-parallel-size 2 \\
        --num-layers 4 --hidden-size 128 --num-attention-heads 4 \\
        --seq-length 128 --micro-batch-size 2 --train-iters 20

Data is synthetic token streams by default (the reference test loop does
the same); pass ``--data-dir`` (a directory of ``*.bin`` shards holding
CHECKSUMMED uint32 token records of seq+1 ids each, written by
``apex_tpu.data.write_checksummed_records``) or ``--data-path`` (the
Megatron flag: explicit shard files in the legacy RAW format — uint32
records of seq+1 ids, no CRC trailer) to stream real tokens through the
fault-tolerant input pipeline (:mod:`apex_tpu.data`), read by
the checkpointable sharded iterator behind the async prefetcher —
damaged records are quarantined, the iterator position rides every
checkpoint (exactly-once resume), and a dying loader thread flushes a
postmortem instead of hanging the run.  ``--save``/``--save-interval``/
``--load`` give checkpoint/resume.
"""

from __future__ import annotations

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental.shard_map import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from apex_tpu import checkpoint as ckpt  # noqa: E402
from apex_tpu import multi_tensor, optimizers  # noqa: E402
from apex_tpu import resilience  # noqa: E402
from apex_tpu.transformer import parallel_state  # noqa: E402
from apex_tpu.transformer.testing import GPTConfig, GPTModel  # noqa: E402
from apex_tpu.transformer.testing.arguments import parse_args  # noqa: E402
from apex_tpu.utils import configure_compile_cache  # noqa: E402


def _extra_args(parser):
    # --data-path / --save / --save-interval / --load come from the
    # Megatron argument clone (arguments.py); only add what it lacks
    g = parser.add_argument_group("pretrain_gpt")
    g.add_argument("--remat-policy", default="attn_res",
                   choices=["full", "dots", "attn_res", "attn_res_mlp",
                            "attn_out"])
    g.add_argument("--data-dir", default=None,
                   help="directory of *.bin token shards (checksummed "
                        "uint32 records of seq+1 ids, "
                        "apex_tpu.data.write_checksummed_records) fed "
                        "through the checkpointable sharded iterator + "
                        "async prefetcher; default: synthetic tokens")
    g.add_argument("--vocab-size", type=int, default=51200,
                   help="unpadded vocab; padded to "
                        "--make-vocab-size-divisible-by x tp")
    g.add_argument("--watchdog-timeout", type=float, default=0.0,
                   help="seconds a train step (its collectives included) "
                        "may run before the collective watchdog logs a "
                        "straggler diagnostic and escalates to the "
                        "grace-period save-and-exit path; 0 disables")
    g.add_argument("--telemetry-dir", default=None,
                   help="write a structured telemetry JSONL stream "
                        "(step events with loss/throughput, ckpt_save, "
                        "watchdog, recompile) under this directory, plus "
                        "a postmortem_*.jsonl flight-recorder dump on "
                        "preemption/escalation; summarize offline with "
                        "`python -m apex_tpu.telemetry summarize`")
    g.add_argument("--profile-every", type=int, default=0,
                   help="with --telemetry-dir: every N steps capture a "
                        "short in-run profiler window and emit "
                        "profile/memory attribution events (per-phase "
                        "device ms, exposed-collective ms, live/peak "
                        "HBM) into the stream; overhead is booked to "
                        "the `profile` goodput bucket and bounded ≤1%; "
                        "0 disables")
    return parser


def build_config(args) -> GPTConfig:
    # pad the vocab so every TP rank gets equal shards (the reference's
    # _vocab_size_with_padding, arguments.py make-vocab-size-divisible-by)
    mult = args.make_vocab_size_divisible_by * args.tensor_model_parallel_size
    args.padded_vocab_size = ((args.vocab_size + mult - 1) // mult) * mult
    # the Megatron argument clone leaves --max-position-embeddings None
    # unless given; a position table shorter than seq_length is asserted
    # against in arguments.py, so seq_length is the only sane default
    if args.max_position_embeddings is None:
        args.max_position_embeddings = args.seq_length
    return GPTConfig(
        num_layers=args.num_layers,
        hidden_size=args.hidden_size,
        num_attention_heads=args.num_attention_heads,
        vocab_size=args.padded_vocab_size,
        max_position_embeddings=args.max_position_embeddings,
        tp_size=args.tensor_model_parallel_size,
        bf16=args.bf16,
        fp16=args.fp16,
        attention_dropout=args.attention_dropout,
        hidden_dropout=args.hidden_dropout,
        use_flash_attention=True,
        remat=args.num_layers >= 12,
        remat_policy=args.remat_policy,
    )


def synthetic_batches(args, key):
    """Yield synthetic (tokens, labels) [global_batch, seq] int32 forever
    (the reference test loop's default)."""
    b, s = args.global_batch_size, args.seq_length
    while True:
        key, k = jax.random.split(key)
        ids = jax.random.randint(k, (b, s + 1), 0,
                                 args.padded_vocab_size, jnp.int32)
        yield ids[:, :-1], ids[:, 1:]


def build_data_iter(args, telemetry=None):
    """The real-token path (ISSUE 7): ``--data-dir`` / ``--data-path``
    shards through :class:`~apex_tpu.data.ShardedRecordIterator`
    (checkpointable, quarantining, retry/re-assign on shard faults)
    behind :class:`~apex_tpu.data.AsyncPrefetcher` (device_put on the
    worker thread, ``data_stall`` telemetry)."""
    import glob

    from apex_tpu.data import AsyncPrefetcher, ShardedRecordIterator
    from apex_tpu.data.records import RECORD_CRC_BYTES

    if args.data_dir:
        paths = sorted(glob.glob(os.path.join(args.data_dir, "*.bin")))
        if not paths:
            raise SystemExit(f"--data-dir {args.data_dir}: no *.bin shards")
        checksummed = True
    else:
        # --data-path keeps its documented legacy format: RAW uint32
        # records of seq+1 ids, no CRC trailer (files written before
        # the checksummed pipeline existed must keep reading — a silent
        # 4-byte frame shift would misalign every record)
        paths = list(args.data_path)
        checksummed = False
    b, s = args.global_batch_size, args.seq_length
    vocab = args.padded_vocab_size

    def decode(mat):
        ids = np.ascontiguousarray(mat).view(np.uint32).reshape(
            b, s + 1).astype(np.int64)
        ids = (ids % vocab).astype(np.int32)
        return ids[:, :-1], ids[:, 1:]

    rb = 4 * (s + 1) + (RECORD_CRC_BYTES if checksummed else 0)
    it = ShardedRecordIterator(
        paths, rb, b, checksummed=checksummed,
        seed=args.seed, decode=decode, telemetry=telemetry,
        slow_read_threshold=1.0)
    return AsyncPrefetcher(
        it, depth=2, telemetry=telemetry,
        transfer=lambda tl: tuple(jax.device_put(x) for x in tl))


def main(argv=None):
    args = parse_args(extra_args_provider=_extra_args, args=argv,
                      defaults={"train_iters": 100, "lr": 1.5e-4})
    tp = args.tensor_model_parallel_size
    n_dev = len(jax.devices())
    if tp < 1 or n_dev % tp:
        raise SystemExit(
            f"--tensor-model-parallel-size {tp} must be >= 1 and divide "
            f"the device count ({n_dev} visible): tp > devices gives an "
            "empty mesh and a non-divisor silently drops devices")
    dp = n_dev // tp
    # the argument clone derives global batch from WORLD_SIZE env (the
    # reference's launcher contract); here the mesh IS the world — one
    # process, all local devices — so re-derive from the actual dp.
    # No gradient-accumulation loop in this example: an explicit
    # --global-batch-size must equal micro x dp.
    args.data_parallel_size = dp
    derived = args.micro_batch_size * dp
    if args.global_batch_size not in (None, derived):
        raise SystemExit(
            f"--global-batch-size {args.global_batch_size} != "
            f"micro-batch-size x dp = {derived}: gradient accumulation "
            "is not wired in this example (see the pipeline schedules "
            "for microbatched training)")
    args.global_batch_size = derived

    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tp, 1, devices=jax.devices()[: tp * dp])
    cfg = build_config(args)
    model = GPTModel(cfg)

    master = model.init_master(jax.random.PRNGKey(args.seed))
    shards = [model.shard_master(master, r) for r in range(tp)]
    params = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)
    opt = optimizers.FusedAdam(
        lr=args.lr, weight_decay=args.weight_decay,
        betas=(args.adam_beta1, args.adam_beta2), eps=args.adam_eps)
    opt_state = opt.init(params)
    clip = args.clip_grad if args.clip_grad and args.clip_grad > 0 else None
    step0 = 0
    if args.load:
        # CRC-verified restore; a corrupt latest checkpoint (killed
        # mid-incident) falls back to the newest intact older one
        (params, opt_state), step0 = resilience.restore_resilient(
            args.load, target=(params, opt_state))
        print(f"resumed from step {step0}")

    dropout_on = cfg.attention_dropout > 0 or cfg.hidden_dropout > 0

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(p, o, tokens, labels, rng):
        def run(p, t, l):
            p = jax.tree_util.tree_map(lambda a: a[0], p)  # this tp shard
            key = rng if dropout_on else None
            loss = jnp.mean(model.apply(p, t, labels=l, dropout_key=key))
            # the reported loss must be the GLOBAL mean, not dp-rank 0's
            # local micro-batch (reference
            # average_losses_across_data_parallel_group)
            return jax.lax.pmean(loss, "data")

        def lossf(p):
            # batch sharded over data, params sharded over tensor; the
            # loss mean is averaged across the data axis
            loss = shard_map(run, mesh=mesh,
                             in_specs=(P("tensor"), P("data"), P("data")),
                             out_specs=P(),
                             check_rep=False)(p, tokens, labels)
            return loss

        loss, g = jax.value_and_grad(lossf)(p)
        if clip is not None:
            g, _ = multi_tensor.clip_grad_norm(g, clip)
        p, o = opt.step(g, o, p)
        return p, o, loss

    if step0 >= args.train_iters:
        print(f"nothing to do: resumed step {step0} >= --train-iters "
              f"{args.train_iters}")
        parallel_state.destroy_model_parallel()
        return None

    # telemetry (ISSUE 4): structured stream + crash flight recorder;
    # step events carry the data-wait/step wall split, the loss rides
    # the windowed batched fetch, and XLA recompiles are surfaced by
    # the jax monitoring listener
    bus = acct = sampler = None
    compile_acc = {"s": 0.0}  # XLA compile wall since the last step
    uninstall_recompile = lambda: None  # noqa: E731
    if args.telemetry_dir:
        from apex_tpu import telemetry as tele

        bus = tele.TelemetryBus(
            run_id=f"pretrain-gpt-{os.getpid()}",
            sinks=[tele.JsonlSink(os.path.join(args.telemetry_dir,
                                               "pretrain_gpt.jsonl"))],
            mesh={"n_devices": tp * dp, "tp": tp, "dp": dp,
                  "platform": jax.devices()[0].platform})
        uninstall_recompile = tele.install_recompile_listener(
            bus, on_duration=lambda s: compile_acc.__setitem__(
                "s", compile_acc["s"] + s))
        acct = bus.accountant(window=args.log_interval)
        if args.profile_every > 0:
            # in-run attribution (ISSUE 9): periodic phase/collective/
            # HBM sampling through the same stream; `summarize` then
            # renders the phase breakdown next to the step percentiles
            sampler = tele.ProfileSampler(bus, every=args.profile_every,
                                          accountant=acct)
        bus.emit("run_start", step=step0, workload="pretrain_gpt",
                 config={"num_layers": args.num_layers,
                         "hidden_size": args.hidden_size,
                         "seq_length": args.seq_length,
                         "global_batch_size": args.global_batch_size,
                         "train_iters": args.train_iters})

    # data (ISSUE 7): real shards ride the checkpointable pipeline —
    # the iterator position is saved with every checkpoint and restored
    # with --load, so a preempted run's sample stream has no duplicates
    # and no drops.  A dying loader thread surfaces as DataLoaderError
    # at next(batches), which lands in the hard-crash handler below and
    # flushes the postmortem.
    use_pipeline = bool(args.data_dir or args.data_path)
    if use_pipeline:
        batches = build_data_iter(args, telemetry=bus)
        if step0:
            ds = ckpt.load_data_state(args.load, step=step0)
            if ds is None:
                raise SystemExit(
                    f"checkpoint step {step0} under --load carries no "
                    "data_state but this run streams real data — "
                    "resuming would silently replay or skip training "
                    "samples (the checkpoint predates the fault-"
                    "tolerant pipeline)")
            batches.load_state_dict(ds)
    else:
        batches = synthetic_batches(args, jax.random.PRNGKey(args.seed + 1))
        for _ in range(step0):
            next(batches)  # a resumed run must not re-see consumed batches

    t0 = time.perf_counter()
    loss = None
    preempted = False

    def _save(step, blocking):
        t_save = time.perf_counter()
        # the iterator position rides the same atomic manifest as the
        # model state (exactly-once resume, docs/data.md)
        ckpt.save_checkpoint(args.save, (params, opt_state), step=step,
                             blocking=blocking,
                             data_state=(batches.state_dict()
                                         if use_pipeline else None))
        if bus is not None:
            dt_save = time.perf_counter() - t_save
            acct.pause(dt_save, "ckpt_fence")
            bus.emit("ckpt_save", step=step, blocking=blocking,
                     wall_ms=round(dt_save * 1e3, 3))

    try:
        with resilience.GracePeriodHandler() as preempt:
            # the watchdog arms a deadline around each collective-bearing
            # step; a hang/straggler logs per-device heartbeats + duration
            # percentiles and lands in the same grace-period exit as
            # SIGTERM
            watchdog = (resilience.Watchdog(args.watchdog_timeout,
                                            handler=preempt)
                        if args.watchdog_timeout > 0 else None)
            if bus is not None and watchdog is not None:
                bus.attach_watchdog(watchdog)

            for it in range(step0, args.train_iters):
                t_data = time.perf_counter()
                tokens, labels = next(batches)
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(args.seed + 2), it)
                t_step = time.perf_counter()
                if watchdog is not None:
                    with watchdog.step(it):
                        params, opt_state, loss = train_step(
                            params, opt_state, tokens, labels, rng)
                        loss.block_until_ready()
                else:
                    params, opt_state, loss = train_step(
                        params, opt_state, tokens, labels, rng)
                if acct is not None:
                    if watchdog is None:
                        # telemetry-grade step timing needs the step's
                        # device wall, not the host dispatch gap; the
                        # watchdog branch already synced.  The next step
                        # consumes these buffers anyway, so this costs
                        # only the host-side dispatch overlap.
                        loss.block_until_ready()
                    now = time.perf_counter()
                    # compile wall inside this step goes to the compile
                    # bucket, not productive goodput; the SCALAR costs
                    # no extra sync — `loss` is a reference the
                    # accountant fetches once per log window
                    compile_s, compile_acc["s"] = compile_acc["s"], 0.0
                    acct.step_done(it + 1, step_s=now - t_step,
                                   data_wait_s=t_step - t_data,
                                   scalars={"loss": loss},
                                   compile_s=compile_s,
                                   timing="synced")
                if sampler is not None:
                    sampler.on_step(it + 1)  # never raises into the run
                if (it + 1) % args.log_interval == 0:
                    dt = (time.perf_counter() - t0) / args.log_interval
                    tok_s = args.global_batch_size * args.seq_length / dt
                    print(f"iter {it + 1}/{args.train_iters} "
                          f"loss {float(loss):.4f} {dt * 1e3:.0f} ms/iter "
                          f"{tok_s:,.0f} tok/s", flush=True)
                    t0 = time.perf_counter()
                if preempt.should_stop:
                    # grace period: make the finished step durable, exit
                    # clean
                    preempted = True
                    if args.save:
                        _save(it + 1, blocking=True)
                    outcome = ("checkpoint written" if args.save
                               else "no --save dir, progress lost")
                    print(f"preempted ({preempt.reason}) at iter {it + 1}: "
                          f"{outcome}, exiting", flush=True)
                    if bus is not None:
                        # machine-readable last-N-steps record next to
                        # the stream — the crash-postmortem half
                        bus.flush_postmortem(preempt.reason or "preempted",
                                             step=it + 1, watchdog=watchdog)
                    break
                if args.save and args.save_interval and \
                        (it + 1) % args.save_interval == 0:
                    # async: the write overlaps the next training steps
                    # and the next save (or exit) fences on it
                    _save(it + 1, blocking=False)
            if watchdog is not None:
                watchdog.close()
    except BaseException as e:
        # hard crash (XLA error, ^C): the postmortem is the record of
        # how the run died — flush it before unwinding, never letting
        # telemetry mask the primary failure
        if bus is not None:
            try:
                bus.flush_postmortem(type(e).__name__)
                acct.finish(reason=type(e).__name__)
                bus.close()
            except Exception:
                pass
        raise
    finally:
        if bus is not None:
            uninstall_recompile()
        if use_pipeline:
            batches.close()
    if args.save and not preempted and not (
            args.save_interval
            and args.train_iters % args.save_interval == 0):
        # the final checkpoint rides the same instrumented path, so its
        # (blocking) write shows up in ckpt_fence/ckpt_save like every
        # other save
        _save(args.train_iters, blocking=True)
    resilience.wait_for_save()
    if bus is not None:
        acct.finish(step=args.train_iters if not preempted else None,
                    reason=(preempt.reason or "preempted") if preempted
                    else "completed")
        bus.close()
    if preempted:
        parallel_state.destroy_model_parallel()
        return float(loss) if loss is not None else None
    assert loss is not None and bool(jnp.isfinite(loss)), "diverged"
    print(f"done: final loss {float(loss):.4f}")
    parallel_state.destroy_model_parallel()
    return float(loss)


if __name__ == "__main__":
    configure_compile_cache()
    main()
