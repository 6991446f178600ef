"""BERT fine-tuning — sequence classification with the binary head.

The reference ships BERT only as a Megatron-toolkit test model; this is
the end-user walkthrough it implies: take the pretrained-style
`BertModel` (bidirectional encoder, [CLS] pooler, varlen attention
masks), put its 2-way head on a downstream classification task, and
fine-tune with the O4-analog policy (bf16 compute, fp32 params — the
usual fine-tuning precision).

Synthetic separable task by default: each "sentence" is classified by
whether its first real token falls in the upper half of the vocab, with
randomly padded lengths so the attention-mask/varlen path is genuinely
exercised.  Accuracy climbs from chance to ~100% in a few hundred
steps; swap :func:`synthetic_task` for a real tokenized dataset.

    python examples/bert_finetune.py --steps 200 --tp 2
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import BertConfig, BertModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.telemetry.metrics import MetricsLogger, StepStats
from apex_tpu.telemetry.spans import phase
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel.layers import state_specs_like


def synthetic_task(rng, n_batches, global_batch, seq, vocab):
    """Variable-length sequences; label = first token in upper vocab
    half.  Returns a list of (tokens, mask, labels)."""
    pool = []
    for _ in range(n_batches):
        tokens = rng.integers(1, vocab, (global_batch, seq))
        lengths = rng.integers(seq // 2, seq + 1, (global_batch,))
        mask = np.arange(seq)[None, :] < lengths[:, None]
        tokens = np.where(mask, tokens, 0)
        labels = (tokens[:, 0] >= vocab // 2).astype(np.int32)
        pool.append((jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(mask),
                     jnp.asarray(labels, jnp.int32)))
    return pool


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4,
                    help="per-dp-rank batch rows")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--opt-level", default="O4")
    ap.add_argument("--dp-ici-size", type=int, default=None,
                    help="hierarchical data parallelism: replicas per "
                         "fast-interconnect group (grad reduces run "
                         "RS(ici)->AR(dcn)->AG(ici))")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8"],
                    help="int8-quantize the DCN leg of the hierarchical "
                         "gradient reduce (requires --dp-ici-size)")
    ap.add_argument("--compress-ici-legs", action="store_true",
                    help="ALSO int8-quantize the ICI RS/AG legs of "
                         "the hierarchical reduce (requires "
                         "--grad-compression int8)")
    ap.add_argument("--no-error-feedback", action="store_true")
    ap.add_argument("--zero3", "--param-shard", action="store_true",
                    dest="zero3",
                    help="full-parameter sharding (ZeRO-3/FSDP): "
                         "params live as 1-D fp32 shards over the "
                         "data axis, gathered per bucket on use; "
                         "grads reduce-scatter into the shard "
                         "(--bucket-mb sizes the gather buckets)")
    ap.add_argument("--fused-opt-tail", action="store_true",
                    help="one multi-tensor optimizer-tail pass over "
                         "packed buffers (bit-identical numerics; see "
                         "docs/optimizers.md)")
    ap.add_argument("--overlap-grad-sync", action="store_true",
                    help="bucket the hierarchical gradient reduce so "
                         "the scheduler can overlap the per-bucket "
                         "collectives (requires --dp-ici-size)")
    ap.add_argument("--bucket-mb", type=float, default=4.0,
                    help="bucket size in MiB for --overlap-grad-sync")
    ap.add_argument("--log-every", type=int, default=50,
                    help="telemetry flush cadence: loss/acc resolve "
                         "every N steps (no per-step host sync)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append structured step metrics here")
    args = ap.parse_args(argv)

    hier = args.dp_ici_size is not None
    if args.grad_compression != "none" and not hier:
        ap.error("--grad-compression requires --dp-ici-size")
    if args.overlap_grad_sync and not hier:
        ap.error("--overlap-grad-sync requires --dp-ici-size")
    if args.compress_ici_legs and args.grad_compression == "none":
        ap.error("--compress-ici-legs requires --grad-compression int8")
    if args.fused_opt_tail and args.tp > 1:
        ap.error("--fused-opt-tail needs replicated params (the "
                 "packed state cannot be tp-sharded; see "
                 "docs/optimizers.md)")
    if args.fused_opt_tail and args.zero3:
        ap.error("--fused-opt-tail packs replicated FusedAdam state; "
                 "--zero3 already runs the update on one flat sharded "
                 "buffer")
    bucket_bytes = int(args.bucket_mb * 1024 * 1024)
    comp = None
    if args.grad_compression != "none":
        from apex_tpu.ops.quantization import CompressionConfig

        comp = CompressionConfig(
            method=args.grad_compression,
            error_feedback=not args.no_error_feedback,
            ici_legs=args.compress_ici_legs,
        )
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=args.tp,
        data_parallel_ici_size_=args.dp_ici_size)
    data_axes = parallel_state.data_parallel_axis_names()
    dp = parallel_state.get_data_parallel_world_size()
    mp = amp.initialize(opt_level=args.opt_level)
    cfg = BertConfig(
        vocab_size=args.vocab, num_layers=args.layers,
        hidden_size=args.hidden, num_attention_heads=args.heads,
        max_position_embeddings=args.seq, policy=mp.policy,
        add_binary_head=True,
    )
    model = BertModel(cfg)
    specs = model.param_specs()
    params = model.init(jax.random.PRNGKey(0))
    if args.zero3:
        from apex_tpu.contrib.optimizers import (
            DistributedFusedAdam,
            reestablish_replicated,
        )

        opt = DistributedFusedAdam(
            lr=args.lr, param_specs=specs,
            axis_name=data_axes if hier else "dp",
            compression=comp, shard_params=True,
            bucket_bytes=bucket_bytes)
        opt.build_layout(params, mesh=mesh)
        shard_spec = opt.shard_spec(model_axes=("tp",))
        opt_specs = opt.state_specs(model_axes=("tp",))
        init_shards = jax.jit(jax.shard_map(
            opt.init_shards, mesh=mesh, in_specs=(specs,),
            out_specs=shard_spec))
    else:
        opt = FusedAdam(lr=args.lr,
                        master_weights=mp.policy.master_weights,
                        fused_tail=args.fused_opt_tail)
        opt_state = opt.init(params)
        opt_specs = state_specs_like(specs, opt_state)

    def cls_loss(p, tokens, mask, labels):
        hidden = model.encode(p, tokens, attention_mask=mask)
        logits = model.binary_logits(p, hidden)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
        acc = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        return (jax.lax.pmean(jnp.mean(nll), data_axes),
                jax.lax.pmean(jnp.mean(acc), data_axes))

    # error-feedback residual state for the compressed reduce
    # (per-BUCKET residuals when the reduce is bucketed; under --zero3
    # the residuals ride the optimizer state instead)
    use_comm = (comp is not None and comp.error_feedback
                and not args.zero3)
    if use_comm:
        from apex_tpu.parallel.distributed import (
            comm_state_specs,
            init_comm_state,
        )

        if args.overlap_grad_sync:
            from apex_tpu.parallel import GradientBuckets

            plan = GradientBuckets.for_tree(
                params, bucket_bytes, param_specs=specs, mesh=mesh)
            comm_state = init_comm_state(
                params, data_axes, comp, mesh=mesh, param_specs=specs,
                buckets=plan)
            comm_specs = comm_state_specs(comm_state, data_axes,
                                          buckets=plan)
        else:
            comm_state = init_comm_state(
                params, data_axes, comp, mesh=mesh, param_specs=specs)
            comm_specs = comm_state_specs(comm_state, data_axes,
                                          param_specs=specs)
    else:
        comm_state, comm_specs = {}, {}

    def train_step(p, s, comm, tokens, mask, labels):
        # --zero3: p is the flat fp32 shard; gather-on-use rebuilds
        # the model-dtype weights per bucket inside the step
        if args.zero3:
            w, s = opt.gather_params(p, s)
            if args.tp > 1:
                w = reestablish_replicated(w, specs)
        else:
            w = p
        with phase("fwd_bwd"):
            (loss, acc), grads = jax.value_and_grad(
                cls_loss, has_aux=True)(w, tokens, mask, labels)
        if args.zero3:
            pass  # the optimizer's reduce-scatter IS the grad sync
        elif hier:
            from apex_tpu.parallel import all_reduce_gradients

            if use_comm:
                grads, comm = all_reduce_gradients(
                    grads, axis_name=data_axes, compression=comp,
                    comm_state=comm,
                    overlap_grad_sync=args.overlap_grad_sync,
                    bucket_bytes=bucket_bytes)
            else:
                grads = all_reduce_gradients(
                    grads, axis_name=data_axes, compression=comp,
                    overlap_grad_sync=args.overlap_grad_sync,
                    bucket_bytes=bucket_bytes)
        else:
            with phase("grad_sync"):
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, "dp"), grads)
        with phase("optimizer"):
            p, s = opt.step(s, grads, p)
        return p, s, comm, loss, acc

    data_spec = P(data_axes if hier else "dp")
    store_spec = shard_spec if args.zero3 else specs
    jstep = jax.jit(
        jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(store_spec, opt_specs, comm_specs,
                      data_spec, data_spec, data_spec),
            out_specs=(store_spec, opt_specs, comm_specs, P(), P()),
        ),
        donate_argnums=(0, 1),
    )

    def eval_fn(p, tokens, mask, labels):
        if args.zero3:
            p, _ = opt.gather_params(p)
            if args.tp > 1:
                p = reestablish_replicated(p, specs)
        return cls_loss(p, tokens, mask, labels)

    jeval = jax.jit(jax.shard_map(
        eval_fn, mesh=mesh,
        in_specs=(store_spec, data_spec, data_spec, data_spec),
        out_specs=(P(), P()),
    ))

    place = lambda t, sp: jax.device_put(
        t, jax.tree.map(lambda s: NamedSharding(mesh, s), sp,
                        is_leaf=lambda x: isinstance(x, P)))
    if args.zero3:
        p = init_shards(place(params, specs))
        s = jax.jit(jax.shard_map(
            opt.init, mesh=mesh, in_specs=(shard_spec,),
            out_specs=opt_specs))(p)
        jax.block_until_ready(p)
        del params  # the shards are the storage — drop the full tree
    else:
        p, s = place(params, specs), place(opt_state, opt_specs)
    cst = place(comm_state, comm_specs)
    global_batch = args.batch * dp
    rng = np.random.default_rng(0)
    # pool large enough that most of the vocab appears in position 0,
    # so eval measures the learned rule rather than memorized rows
    train_pool = synthetic_task(rng, 64, global_batch, args.seq,
                                args.vocab)
    eval_pool = synthetic_task(np.random.default_rng(1),
                               args.eval_batches, global_batch,
                               args.seq, args.vocab)

    # async harvesting: loss/acc stay device futures between flushes —
    # no per-step host sync; ms/step excludes the first-step compile
    # (stats.begin blocks on step 0, the clock starts after)
    stats = StepStats(tokens_per_step=global_batch, unit="seq")
    with MetricsLogger(jsonl_path=args.metrics_jsonl,
                       flush_every=args.log_every, stats=stats,
                       run="bert_finetune") as tlm:
        loss = acc = None
        for i in range(args.steps):
            tokens, mask, labels = train_pool[i % len(train_pool)]
            p, s, cst, loss, acc = jstep(p, s, cst, tokens, mask, labels)
            if i == 0:
                stats.begin((loss, acc))
            else:
                stats.tick()
            tlm.log_scalars(i, loss=loss, train_acc=acc)
        summary = stats.summary((loss, acc))
    if summary.get("timed_steps"):
        print(f"{summary['ms_per_step']:.1f} ms/step  "
              f"{summary['tokens_per_sec']:,.0f} seq/s")

    accs = [float(jeval(p, *b)[1]) for b in eval_pool]
    print(f"eval accuracy: {np.mean(accs):.3f}")
    return {"eval_accuracy": float(np.mean(accs))}


if __name__ == "__main__":
    main()
