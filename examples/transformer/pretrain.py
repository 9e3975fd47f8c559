"""Config-driven GPT/BERT pretraining (BASELINE configs 3 and 4).

Capability port of the reference pretrain entries
(tests/L0/run_transformer/run_gpt_minimal_test.py + megatron's
pretrain_{gpt,bert}.py pattern) driven by the Megatron argument bundle
(apex_tpu.transformer.testing.arguments).

TPU-first loop shape: the reference dispatches one fwd/bwd per Python step
(torch eager); here ``log_interval`` training steps run inside ONE jitted
``lax.scan`` dispatch over the (dp, tp) mesh — the host only sees a loss
trace per chunk. Synthetic data (the reference minimal tests use synthetic
ids too).

Run (BERT-large + FusedLAMB, BASELINE config 3):
    python examples/transformer/pretrain.py --model bert \
        --num-layers 24 --hidden-size 1024 --num-attention-heads 16 \
        --max-position-embeddings 512 --seq-length 512 \
        --micro-batch-size 4 --optimizer lamb --lr 1e-4 --bf16 \
        --train-iters 30 --log-interval 10

GPT-2 345M TP (BASELINE config 4): --model gpt --num-layers 24
    --hidden-size 1024 ... --tensor-model-parallel-size 2
"""

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import compile_cache
from apex_tpu.amp.scaler import LossScaler
from apex_tpu.optimizers.fused_adam import fused_adam
from apex_tpu.optimizers.fused_lamb import fused_lamb
from apex_tpu.optimizers.fused_sgd import fused_sgd
from apex_tpu.telemetry import spans
from apex_tpu.transformer.parallel_state import DATA_AXIS, TENSOR_AXIS
from apex_tpu.transformer.testing import (
    BertModel,
    GPTModel,
    global_vars,
    parse_args,
)


def _extra_args(parser):
    parser.add_argument("--model", choices=("gpt", "bert"), default="gpt")
    parser.add_argument("--vocab-size", type=int, default=50257)
    return parser


def make_lr_schedule(args):
    """Warmup + {constant|linear|cosine} decay to min_lr, driven by the
    Megatron lr arg group (reference: the AnnealingLR scheduler those
    args configure). Returns a jit-safe ``step -> lr`` callable; the
    fused optimizers call it with their on-device step count."""
    base, mn = args.lr, args.min_lr
    decay_iters = args.lr_decay_iters or args.train_iters
    warmup = args.lr_warmup_iters
    if args.lr_warmup_fraction is not None:
        warmup = int(args.lr_warmup_fraction * decay_iters)
    style = args.lr_decay_style

    def sched(step):
        step = jnp.asarray(step, jnp.float32)
        warm_lr = base * step / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(decay_iters - warmup, 1),
                        0.0, 1.0)
        if style == "constant":
            decayed = jnp.asarray(base, jnp.float32)
        elif style == "linear":
            decayed = base - (base - mn) * frac
        elif style == "cosine":
            decayed = mn + (base - mn) * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
        else:
            raise ValueError(f"unknown lr_decay_style {style!r}")
        return jnp.where(step < warmup, warm_lr, decayed)

    return sched


def make_optimizer(args):
    """args.optimizer → fused transform (reference _add_training_args
    --optimizer {adam,sgd} + the LAMB path of the BERT recipe), with the
    lr arg group's warmup/decay schedule."""
    lr = make_lr_schedule(args)
    if args.optimizer == "adam":
        return fused_adam(learning_rate=lr, betas=(args.adam_beta1,
                                                   args.adam_beta2),
                          eps=args.adam_eps, weight_decay=args.weight_decay)
    if args.optimizer == "lamb":
        return fused_lamb(learning_rate=lr, betas=(args.adam_beta1,
                                                   args.adam_beta2),
                          eps=args.adam_eps, weight_decay=args.weight_decay)
    if args.optimizer == "sgd":
        return fused_sgd(learning_rate=lr, momentum=args.sgd_momentum,
                         weight_decay=args.weight_decay)
    raise ValueError(f"unknown optimizer {args.optimizer}")


def main(argv=None):
    # no-op unless launched by ``python -m apex_tpu.parallel.multiproc``;
    # afterwards jax.devices() is the GLOBAL list and the (dp, tp) mesh
    # spans hosts (collectives ride ICI within a host, DCN across)
    from apex_tpu.parallel.multiproc import init_distributed

    init_distributed()
    # the train step is the expensive compile of a run: keep it in the
    # persistent cache
    compile_cache.activate()
    devices = jax.devices()
    args = global_vars.set_global_variables(
        argv, extra_args_provider=_extra_args,
        world_size=len(devices), ignore_unknown_args=False)
    args.rank = jax.process_index()

    tp = args.tensor_model_parallel_size
    if args.pipeline_model_parallel_size != 1:
        raise NotImplementedError(
            "pretrain.py drives the (dp, tp) mesh; pipeline-parallel "
            "training lives in apex_tpu.transformer.testing.minimal")
    dp = args.data_parallel_size
    mesh = Mesh(np.asarray(devices[:dp * tp]).reshape(dp, tp),
                (DATA_AXIS, TENSOR_AXIS))

    vocab = args.pad_vocab_size(args.vocab_size)
    cfg = args.to_transformer_config()
    s = args.seq_length
    b_local = args.micro_batch_size  # per-dp-rank batch
    model_cls = GPTModel if args.model == "gpt" else BertModel
    model = model_cls(cfg)

    # every process builds the same full batch (same seed) and places it
    # ONCE onto the global dp-sharded layout — host numpy is a valid
    # multi-process input but would re-stage host->device every chunk
    from jax.sharding import NamedSharding

    rs = np.random.RandomState(args.seed)
    sh_data = NamedSharding(mesh, P(DATA_AXIS))
    # everything in this (dp, tp) entry is replicated outside shard_map
    repl = NamedSharding(mesh, P())
    ids = jax.device_put(
        rs.randint(0, vocab, (dp * b_local, s)).astype(np.int32), sh_data)
    labels = jax.device_put(
        rs.randint(0, vocab, (dp * b_local, s)).astype(np.int32), sh_data)
    pos = jax.device_put(
        np.ascontiguousarray(np.broadcast_to(
            np.arange(s, dtype=np.int32)[None], ids.shape)), sh_data)

    # amp O2: half-precision compute with a dynamic loss scale unless
    # --loss-scale pins a static one; fp32 runs unscaled
    half = args.fp16 or args.bf16
    scaler = LossScaler(loss_scale="dynamic"
                        if half and args.loss_scale is None
                        else float(args.loss_scale or 1.0))
    tx = make_optimizer(args)

    def fwd_loss(p, ids, pos, labels, scale):
        mutable = ["intermediates"] if cfg.num_moe_experts else False
        if args.model == "gpt":
            out = model.apply({"params": p}, ids, pos, None, labels,
                              mutable=mutable)
        else:
            out = model.apply({"params": p}, ids, jnp.ones_like(ids),
                              lm_labels=labels, mutable=mutable)
        if mutable:
            out, new_vars = out
        per_tok = out[0] if args.model == "bert" else out
        loss = jnp.mean(per_tok)
        if mutable:
            # Switch aux loss: explicit objective term, not a side effect
            from apex_tpu.transformer.moe import collect_moe_aux

            loss = loss + cfg.moe_aux_loss_coeff * collect_moe_aux(
                new_vars["intermediates"])
        return loss * scale

    def init_fn(ids, pos, labels):
        if args.model == "gpt":
            return model.init(jax.random.PRNGKey(args.seed), ids, pos,
                              None)["params"]
        return model.init(jax.random.PRNGKey(args.seed), ids,
                          jnp.ones_like(ids))["params"]

    def chunk_fn(n_steps):
        """n_steps training steps under one dispatch."""
        def local(params, opt_state, scaler_state, ids, pos, labels):
            def body(carry, _):
                # the scopes name each op's stretch of the step in the
                # device trace (``tf_op``) and the compiled HLO
                p, o, ss = carry
                with jax.named_scope("fwd_bwd"):
                    scale = scaler.scale(jnp.float32(1.0), ss)
                    loss, grads = jax.value_and_grad(fwd_loss)(
                        p, ids, pos, labels, scale)
                with jax.named_scope("grad_pmean"):
                    grads = jax.tree_util.tree_map(
                        lambda g: lax.pmean(g, DATA_AXIS), grads)
                with jax.named_scope("unscale"):
                    grads, found_inf = scaler.unscale(grads, ss)
                    found_inf = lax.pmax(found_inf, TENSOR_AXIS)
                    nss = scaler.update(ss, found_inf)
                with jax.named_scope("optimizer"):
                    updates, no = tx.update(grads, o, p)
                with jax.named_scope("apply_update"):
                    np_ = jax.tree_util.tree_map(
                        lambda a, u: jnp.where(found_inf, a,
                                               a + u.astype(a.dtype)),
                        p, updates)
                    no = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(found_inf, old, new),
                        no, o)
                return (np_, no, nss), lax.pmean(loss, DATA_AXIS) / scale

            carry, losses = lax.scan(
                body, (params, opt_state, scaler_state), jnp.arange(n_steps))
            return carry + (losses,)

        def step(params, opt_state, scaler_state, ids, pos, labels):
            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS),
                          P(DATA_AXIS)),
                out_specs=P(), check_vma=False)(
                params, opt_state, scaler_state, ids, pos, labels)

        return jax.jit(step, donate_argnums=(0, 1, 2))

    # each set-up span ends when its call returns, not when the device
    # is done: trace + lower + compile or cache load, and the enqueue
    with spans.span("trainer.setup.init"):
        params = jax.jit(jax.shard_map(
            init_fn, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=P(), check_vma=False))(ids, pos, labels)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    # the initial state carries the sharding the step returns
    # (replicated over the mesh): fed back as chunk 2's input, the
    # step's outputs then hit chunk 1's executable instead of compiling
    # the same program again under another input sharding
    with spans.span("trainer.setup.opt_init"):
        opt_state = jax.jit(lambda p: tx.init(p),
                            out_shardings=repl)(params)
        scaler_state = jax.device_put(
            jax.tree_util.tree_map(np.asarray, scaler.init()), repl)

    # --- checkpoint/resume (reference checkpointing args :646-669) ---
    start_iter = 0
    if args.load:
        with spans.span("trainer.setup.load"):
            from apex_tpu import checkpoint as ckpt_mod

            # restore directly onto the replicated mesh sharding (a plain
            # concrete template would inherit whatever mix of committed
            # devices each state happened to be created on)
            with ckpt_mod.CheckpointManager(args.load) as lm:
                step0 = lm.latest_step()
                # keys None = metadata unreadable → optimistically try the
                # full restore (a failure there surfaces, as it should)
                keys = lm.tree_keys(step0) if step0 is not None else None
                # --finetune loads weights ONLY (megatron semantics): a
                # restored optimizer count would pin the lr schedule at the
                # old run's decay floor
                full = (step0 is not None and not args.no_load_optim
                        and not args.finetune
                        and (keys is None or "opt" in keys))
                if step0 is not None and full:
                    tmpl = {"params": ckpt_mod.abstract_like(params, repl),
                            "opt": ckpt_mod.abstract_like(opt_state, repl),
                            "scaler": ckpt_mod.abstract_like(scaler_state,
                                                             repl)}
                    restored = lm.restore(step0, tmpl)
                    params = restored["params"]
                    opt_state = restored["opt"]
                    scaler_state = restored["scaler"]
                elif step0 is not None:
                    # params-only: checkpoint was written with
                    # --no-save-optim, or --no-load-optim was passed
                    # (megatron's warn-and-continue posture)
                    if (args.rank == 0 and not args.no_load_optim
                            and not args.finetune):
                        # reached without an explicit weights-only flag: the
                        # checkpoint itself lacks the opt subtree
                        print("checkpoint has no optimizer state (saved with "
                              "--no-save-optim); loading params only",
                              flush=True)
                    params = lm.restore(
                        step0,
                        {"params": ckpt_mod.abstract_like(params, repl)},
                        partial=True)["params"]
        if step0 is None:
            # the Megatron posture: warn loudly, start from scratch
            if args.rank == 0:
                print(f"WARNING: no checkpoint found in {args.load}; "
                      "training from random initialization", flush=True)
        else:
            if not args.finetune:
                start_iter = step0
            if args.rank == 0:
                print(f"loaded checkpoint {args.load} @ iter {step0}"
                      f"{' (finetune: iter reset)' if args.finetune else ''}",
                      flush=True)

    save_mgr = None
    if args.save:
        from apex_tpu import checkpoint as ckpt_mod

        save_mgr = ckpt_mod.CheckpointManager(args.save)

    def save_state(step):
        # orbax's FixedIntervalPolicy saves only at step % N == 0, which
        # a chunked step grid (done = start + k*log_n) can miss forever —
        # the interval-crossing check below throttles instead, so the
        # manager itself is un-throttled; skip steps that already exist
        # (e.g. rerunning into a dir left by a longer previous run)
        if save_mgr is None or step in save_mgr.all_steps():
            return
        state = {"params": params} if args.no_save_optim else {
            "params": params, "opt": opt_state, "scaler": scaler_state}
        save_mgr.save(step, state)

    log_n = max(1, min(args.log_interval, args.train_iters))
    run_chunk = chunk_fn(log_n)

    if args.rank == 0:
        print(f"{args.model} pretrain | params {n_params/1e6:.1f}M | "
              f"mesh dp={dp} tp={tp} | mbs {b_local} seq {s} | "
              f"opt {args.optimizer}", flush=True)

    done = start_iter
    if done >= args.train_iters and args.rank == 0:
        print(f"checkpoint iter {done} >= --train-iters "
              f"{args.train_iters}: nothing left to train (pass "
              "--finetune to reset the iteration count)", flush=True)
    first_chunk = True
    last_loss = float("nan")
    tokens_per_sec = 0.0
    compile_and_run = None
    chunks = []
    mark = time.perf_counter()   # where the chunk before ended
    while done < args.train_iters:
        with spans.span("trainer.chunk", steps=log_n) as chunk:
            with spans.span("chunk.dispatch"):
                # returns once the chunk is enqueued; the first call
                # holds trace + lower + compile or cache load
                params, opt_state, scaler_state, losses = run_chunk(
                    params, opt_state, scaler_state, ids, pos, labels)
            with spans.span("chunk.fetch"):
                # fetching the chunk's losses waits for the device
                losses = np.asarray(losses)
            with spans.span("chunk.host"):
                last_loss = float(losses[-1])
                done += log_n
                # save when a multiple of save_interval falls inside
                # this chunk (correct on any chunk grid, aligned or not)
                if args.save_interval and done % args.save_interval < log_n:
                    save_state(done)
                now = time.perf_counter()
                elapsed, mark = now - mark, now
                # per-chunk record for callers that check a run
                # (chip_smoke.py): wall seconds since the chunk before
                # was fetched and saved, when the chunk ended, how many
                # executables the step has compiled so far (1 once
                # warm), and the bytes each local device holds while
                # the train state is live (None where the backend keeps
                # no memory stats)
                chunks.append({
                    "iter": done, "losses": losses.tolist(),
                    "seconds": elapsed, "t_end": now,
                    "programs": run_chunk._cache_size(),
                    "bytes_in_use": {
                        str(d.id): (d.memory_stats() or {}).get(
                            "bytes_in_use")
                        for d in jax.local_devices()}})
                chunk.set(iter=done, programs=chunks[-1]["programs"])
                if first_chunk:
                    first_chunk = False
                    # first chunk includes compile; don't count it in
                    # throughput
                    compile_and_run = elapsed
                    if args.rank == 0:
                        print(f" iter {done}: loss {last_loss:.4f} "
                              f"(first chunk incl. compile "
                              f"{compile_and_run:.1f}s)", flush=True)
                    continue
                tokens_per_sec = log_n * dp * b_local * s / elapsed
                if args.rank == 0:
                    print(f" iter {done}: loss {last_loss:.4f}  "
                          f"{tokens_per_sec:,.0f} tokens/s  "
                          f"({elapsed/log_n*1e3:.1f} ms/iter)", flush=True)
    if tokens_per_sec == 0.0 and compile_and_run:
        # single-chunk run: report throughput from the compile chunk rather
        # than a misleading 0 (flagged as compile-inclusive)
        tokens_per_sec = log_n * dp * b_local * s / compile_and_run
        if args.rank == 0:
            print(f" tokens/s {tokens_per_sec:,.0f} "
                  "(single chunk, INCLUDES compile)", flush=True)

    if save_mgr is not None:
        save_state(done)  # final state (no-op if that step exists)
        save_mgr.close()

    global_vars.destroy_global_vars()
    from apex_tpu.transformer.pipeline_parallel.utils import (
        destroy_microbatch_calculator,
    )
    try:
        destroy_microbatch_calculator()
    except Exception:
        pass
    return {"loss": last_loss, "tokens_per_sec": tokens_per_sec,
            "n_params": n_params, "chunks": chunks,
            "loss_scale": float(np.asarray(scaler_state.loss_scale)),
            "unskipped": int(np.asarray(scaler_state.unskipped)),
            "overflow": bool(np.asarray(scaler_state.overflow))}


if __name__ == "__main__":
    main()
