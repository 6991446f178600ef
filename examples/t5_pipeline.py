"""Encoder-decoder (T5-style) training across a pipeline split — the
example for `ModelType.encoder_and_decoder` (reference capability:
pipeline_model_parallel_split_rank in apex/transformer/parallel_state.py
+ schedules/common.py; the reference ships no runnable enc-dec example,
this framework does).

Stages [0, split) run the encoder, [split, pp) the decoder; the
cross-attention memory rides the ppermute ring with its microbatch
(apex_tpu.transformer.pipeline_parallel.pipeline_encdec).

Runs anywhere: real TPU chips or virtual CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu).

    python examples/t5_pipeline.py
    # hierarchical dp with an int8-compressed DCN leg:
    python examples/t5_pipeline.py --dp-ici-size 2 --grad-compression int8
"""

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models import T5Config, T5Model
from apex_tpu.optimizers import FusedAdam
from apex_tpu.telemetry.metrics import MetricsLogger, StepStats
from apex_tpu.telemetry.spans import phase
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel.layers import state_specs_like

VOCAB = 128
STEPS = 60


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--log-every", type=int, default=10,
                    help="telemetry flush cadence: the loss resolves "
                         "every N steps (no per-step host sync)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append structured step metrics here")
    ap.add_argument("--dp-ici-size", type=int, default=None,
                    help="hierarchical data parallelism: replicas per "
                         "fast-interconnect group")
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
                    help="full-parameter sharding (ZeRO-3/FSDP) over "
                         "the data axis, composed with the pipeline: "
                         "each pp stage keeps its local stack as a "
                         "1-D fp32 shard, gathered per bucket on use")
    ap.add_argument("--bucket-mb-zero3", type=float, default=None,
                    help="ZeRO-3 gather bucket size in MiB "
                         "(defaults to --bucket-mb)")
    ap.add_argument("--overlap-grad-sync", action="store_true",
                    help="bucket the hierarchical gradient reduce so "
                         "the scheduler can overlap the per-bucket "
                         "collectives (requires --dp-ici-size)")
    ap.add_argument("--bucket-mb", type=float, default=4.0,
                    help="bucket size in MiB for --overlap-grad-sync")
    args = ap.parse_args(argv)

    hier = args.dp_ici_size is not None
    if args.grad_compression != "none" and not hier:
        ap.error("--grad-compression requires --dp-ici-size")
    if args.overlap_grad_sync and not hier:
        ap.error("--overlap-grad-sync requires --dp-ici-size")
    if args.compress_ici_legs and args.grad_compression == "none":
        ap.error("--compress-ici-legs requires --grad-compression int8")
    bucket_bytes = int(args.bucket_mb * 1024 * 1024)
    comp = None
    if args.grad_compression != "none":
        from apex_tpu.ops.quantization import CompressionConfig

        comp = CompressionConfig(
            method=args.grad_compression,
            error_feedback=not args.no_error_feedback,
            ici_legs=args.compress_ici_legs,
        )

    n = jax.device_count()
    pp = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    if pp < 2:
        raise SystemExit("need >= 2 devices for a pipeline split "
                         "(set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8 JAX_PLATFORMS=cpu)")
    if hier and n // pp % args.dp_ici_size:
        raise SystemExit(f"data extent {n // pp} is not divisible by "
                         f"--dp-ici-size {args.dp_ici_size}")
    split = pp // 2
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=pp,
        pipeline_model_parallel_split_rank_=split,
        data_parallel_ici_size_=args.dp_ici_size,
    )
    data_axes = parallel_state.data_parallel_axis_names()
    dp = parallel_state.get_data_parallel_world_size()
    print(f"devices={n} pp={pp} (enc stages {split}, dec {pp - split}) dp={dp}")

    model = T5Model(T5Config(
        vocab_size=VOCAB,
        num_encoder_layers=split * 2,
        num_decoder_layers=(pp - split) * 2,
        hidden_size=64,
        num_attention_heads=4,
        max_position_embeddings=32,
        compute_dtype=jnp.float32,
        remat=False,
        attention_impl="xla",
    ))
    params = model.pipeline_params(model.init(jax.random.PRNGKey(0)))
    specs = model.pipeline_param_specs()
    # no --fused-opt-tail here: the tail packs REPLICATED param state,
    # and this trainer's params are always pp-stacked (the packed
    # buffers cannot be described by a PartitionSpec — see
    # docs/optimizers.md "Fused optimizer tail" scope note).  --zero3
    # composes fine: each (pp, tp) position runs its own data-axis
    # shard of its local stack (model_axes in every spec below)
    if args.zero3:
        from apex_tpu.contrib.optimizers import (
            DistributedFusedAdam,
            reestablish_replicated,
        )

        zb = args.bucket_mb_zero3
        opt = DistributedFusedAdam(
            lr=3e-3, param_specs=specs,
            axis_name=data_axes if hier else "dp",
            compression=comp, shard_params=True,
            bucket_bytes=int((args.bucket_mb if zb is None else zb)
                             * 1024 * 1024))
        opt.build_layout(params, mesh=mesh)
        shard_spec = opt.shard_spec(model_axes=("pp", "tp"))
        opt_specs = opt.state_specs(model_axes=("pp", "tp"))
        init_shards = jax.jit(jax.shard_map(
            opt.init_shards, mesh=mesh, in_specs=(specs,),
            out_specs=shard_spec))
    else:
        opt = FusedAdam(lr=3e-3)
        opt_state = opt.init(params)
        opt_specs = state_specs_like(specs, opt_state)

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

    def train_step(params, opt_state, comm, enc, dec, tgt):
        # flat dp: no explicit grad-pmean needed — pipeline_loss pmeans
        # the loss over "dp" internally, so differentiating it inserts
        # the dp grad reduction automatically (shard_map's replication
        # check on out_specs would reject divergent updates otherwise).
        # Hierarchical dp: the internal pmean rides the size-1 dummy
        # axis, so the data mean over (dcn, ici) happens explicitly —
        # RS(ici) -> AR(dcn, int8 when compressed) -> AG(ici)
        # --zero3: gather the local stack's weights per bucket first,
        # re-establishing the replicated typing over pp/tp the
        # pipeline collectives expect
        if args.zero3:
            weights, opt_state = opt.gather_params(params, opt_state)
            weights = reestablish_replicated(weights, specs)
        else:
            weights = params
        with phase("fwd_bwd"):
            loss, grads = jax.value_and_grad(
                lambda p: model.pipeline_loss(p, enc, dec, tgt,
                                              num_microbatches=2)
            )(weights)
        if args.zero3:
            if hier:
                loss = jax.lax.pmean(loss, data_axes)
        elif hier:
            from apex_tpu.parallel import all_reduce_gradients

            loss = jax.lax.pmean(loss, data_axes)
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
        with phase("optimizer"):
            params, opt_state = opt.step(opt_state, grads, params)
        return params, opt_state, comm, loss

    data_spec = P(data_axes if hier else "dp")
    store_spec = shard_spec if args.zero3 else specs
    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(store_spec, opt_specs, comm_specs,
                  data_spec, data_spec, data_spec),
        out_specs=(store_spec, opt_specs, comm_specs, P()),
    ))
    place = lambda tree, sp: jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), sp,
                           is_leaf=lambda x: isinstance(x, P)))

    # toy copy task: decode the reversed source sequence
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    enc_tokens = jax.random.randint(ks[0], (4 * dp, 16), 0, VOCAB)
    dec_tokens = jnp.flip(enc_tokens, axis=1)
    targets = jnp.roll(dec_tokens, -1, axis=1)

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
    # async harvesting: the loss stays a device future between flushes
    # — no per-step host sync; ms/step excludes the first-step compile
    # (stats.begin blocks on step 0, the clock starts after), the same
    # timing contract as the other example trainers
    stats = StepStats(tokens_per_step=dec_tokens.shape[0]
                      * dec_tokens.shape[1])
    with MetricsLogger(jsonl_path=args.metrics_jsonl,
                       flush_every=args.log_every, stats=stats,
                       run="t5_pipeline") as tlm:
        loss = None
        for i in range(STEPS):
            p, s, cst, loss = step(p, s, cst, enc_tokens, dec_tokens,
                                   targets)
            if i == 0:
                stats.begin(loss)
            else:
                stats.tick()
            tlm.log_scalars(i, loss=loss)
        summary = stats.summary(loss)
    if summary.get("timed_steps"):
        print(f"{summary['ms_per_step']:.1f} ms/step  "
              f"{summary['tokens_per_sec']:,.0f} dec tokens/s")
    print("done")


if __name__ == "__main__":
    main()
