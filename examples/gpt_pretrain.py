"""GPT pretraining — the full production stack in one script.

The "switch from the reference and find everything" walkthrough: what
`apex.amp` + `apex.transformer` + `apex.contrib.optimizers` users
assemble from Megatron pieces, wired TPU-native end to end:

- 4-axis mesh (``dp x pp x cp x tp``) from one initialize call;
- precision `Policy` driving every dtype through one config kwarg
  (O5 bf16 default; pass ``--opt-level O2`` for fp16 + dynamic scaler);
- the dispatched 1F1B pipeline schedule (``pipeline_1f1b_grads``) with
  microbatch gradient accumulation;
- FusedAdam with fp32 masters, ``--zero`` for the reduce-scatter /
  all-gather sharded ``DistributedFusedAdam``, or ``--zero3`` for
  FULL-parameter sharding (gather-on-use weights, sharded update, no
  replicated copy — the h≥4096-class memory unlock);
- dynamic loss scaling with model-parallel overflow consensus (fp16
  levels only — bf16 needs none);
- async, atomic checkpointing + SIGTERM-safe autoresume;
- structured telemetry (apex_tpu.telemetry): the loss is held as an
  unresolved device future and resolved only at the ``--log-every``
  flush cadence — NO per-step ``float(loss)`` host sync, so XLA's
  async dispatch stays ahead of the host — with live tokens/s + MFU,
  subsystem events (checkpoint/guard/comm) in the ``--metrics-jsonl``
  stream, phase-annotated traces and an on-demand trace trigger
  (touch ``<--trace-dir>/TRACE_REQUEST`` mid-run).

Synthetic token stream by default; swap :func:`batches` for a real
tokenized corpus.

    python examples/gpt_pretrain.py --tp 2 --pp 2 --num-micro 4 \
        --steps 50 --checkpoint-dir /tmp/gpt_ck
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.telemetry import programs as _programs
from apex_tpu.telemetry.metrics import (
    MetricsLogger,
    StepStats,
    transformer_flops_per_token,
)
from apex_tpu.telemetry.spans import TraceTrigger, phase
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.amp import model_parallel_all_finite
from apex_tpu.transformer.tensor_parallel.layers import state_specs_like
from apex_tpu.transformer.tensor_parallel import clip_grad_norm
from apex_tpu.utils.autoresume import AutoResume


def batches(rng, n_batches, global_batch, seq, vocab):
    """Pre-generated synthetic LM batches (see --data for a corpus).

    Token ids are Zipf-distributed (p ∝ 1/rank), like text: a uniform
    stream sits at its entropy floor ln(vocab) from step 0, so its loss
    cannot fall and a few-step run could not tell a working optimizer
    from a broken one."""
    p = 1.0 / np.arange(1, vocab + 1)
    p /= p.sum()
    pool = []
    for _ in range(n_batches):
        tokens = jnp.asarray(
            rng.choice(vocab, size=(global_batch, seq), p=p), jnp.int32)
        pool.append((tokens, jnp.roll(tokens, -1, axis=1)))
    return pool


def file_batches(path, n_batches, global_batch, seq, vocab):
    """Real-corpus pool from an apex_tpu.data mmap token file: windows
    via IndexedTokenDataset, order via MegatronPretrainingSampler (the
    whole global batch is materialized here and dp-sharded by the
    step's P("dp") in_spec, so the sampler runs as one logical rank)."""
    from apex_tpu.data import IndexedTokenDataset, pretraining_batches
    from apex_tpu.transformer.data import MegatronPretrainingSampler

    ds = IndexedTokenDataset(path, seq_len=seq)
    if ds.max_token >= vocab:
        raise ValueError(
            f"{path}: corpus max token id {ds.max_token} >= model vocab "
            f"{vocab} — out-of-range ids would train on clamped/masked "
            f"embeddings silently")
    sampler = MegatronPretrainingSampler(
        total_samples=len(ds), consumed_samples=0,
        micro_batch_size=global_batch,
        data_parallel_rank=0, data_parallel_size=1,
    )
    pool = []
    for toks, tgts in pretraining_batches(ds, sampler):
        pool.append((jnp.asarray(toks), jnp.asarray(tgts)))
        if len(pool) >= n_batches:
            break
    if not pool:
        raise ValueError(f"{path}: fewer than {global_batch} windows")
    return pool


def dp_mean_where_varying(grads, specs):
    """The dp gradient sum happens ONCE: ``pmean(g, "dp")`` for the
    leaves whose gradient still varies over dp, nothing for the rest.

    ``model.loss`` averages over dp inside the differentiated function,
    and the weights come in typed dp-invariant, so jax's own transposes
    have already summed every replicated leaf's gradient over dp (in
    the backward loop, layer by layer) and typed it dp-invariant.  A
    pmean of an invariant value is NOT free: jax ``pvary``s it and XLA
    all-reduces (g + g) / 2 — the whole gradient tree over the wire a
    second time.  What is left to average is a gradient taken with
    respect to weights cast dp-varying (the ``parallel.Reducer`` idiom:
    local gradients, reduced later); its type says so.  The step's
    ``out_specs`` refuse a dp-varying parameter at trace time, so a
    wrong skip cannot pass silently.  dp-SHARDED leaves (MoE experts
    riding dp as ep) are already final via the all_to_all transpose and
    must NOT be averaged elementwise across unrelated experts."""
    return jax.tree.map(
        lambda g, sp: (jax.lax.pmean(g, "dp")
                       if "dp" in jax.typeof(g).vma
                       and "dp" not in parallel_state.spec_axis_names(sp)
                       else g),
        grads, specs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--num-micro", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--micro-batch", type=int, default=2,
                    help="per-dp-rank microbatch rows")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--opt-level", default="O5",
                    help="O0..O5 — fp16 levels add dynamic loss scaling")
    ap.add_argument("--zero", action="store_true",
                    help="shard optimizer state over dp "
                         "(DistributedFusedAdam)")
    ap.add_argument("--zero3", "--param-shard", action="store_true",
                    dest="zero3",
                    help="FULL-parameter sharding (ZeRO-3/FSDP): "
                         "params live as 1-D fp32 shards over the "
                         "data axis and are all-gathered to model "
                         "dtype per bucket ON USE (--bucket-mb sizes "
                         "the buckets); grads reduce-scatter straight "
                         "into the shard and the update runs there — "
                         "per-device state bytes drop ~world-fold, "
                         "unlocking models replicated DDP cannot "
                         "hold.  Checkpoints store the shard buffer "
                         "(resume at the same dp topology; "
                         "see docs/distributed.md)")
    ap.add_argument("--dp-ici-size", type=int, default=None,
                    help="split data parallelism into a (dcn, ici) "
                         "hierarchy with this many replicas per "
                         "fast-interconnect group; gradient reduces "
                         "then run RS(ici)->AR(dcn)->AG(ici) so only "
                         "1/ici of the bytes cross the slow axis")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8"],
                    help="quantize the DCN leg of the hierarchical "
                         "gradient reduce (requires --dp-ici-size); "
                         "ICI legs and gradient dtypes are untouched")
    ap.add_argument("--compression-block", type=int, default=256,
                    help="elements per fp32 scale in the quantized leg")
    ap.add_argument("--compression-rounding", default="nearest",
                    choices=["nearest", "stochastic"])
    ap.add_argument("--compress-ici-legs", action="store_true",
                    help="ALSO int8-quantize the ICI reduce-scatter/"
                         "all-gather legs of the hierarchical reduce "
                         "(EQuARX's ICI half; requires "
                         "--grad-compression int8) — ~4x fewer bytes "
                         "on the fast links too")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="drop the quantization-residual compensation "
                         "state (lossier; mainly for A/B experiments)")
    ap.add_argument("--fused-opt-tail", action="store_true",
                    help="run the optimizer tail as ONE multi-tensor "
                         "pass over bucketed buffers (moments/masters "
                         "stored packed — bit-identical numerics, "
                         "fewer HBM passes; checkpoints are NOT "
                         "layout-compatible with the per-leaf state). "
                         "FusedAdam path only (--zero shards its own "
                         "flat buffer already)")
    ap.add_argument("--exp-avg-sq-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="storage dtype of Adam's second moment "
                         "(bfloat16 halves its bytes in the fused "
                         "tail; math stays fp32 — see "
                         "docs/optimizers.md for when it is safe)")
    ap.add_argument("--overlap-grad-sync", action="store_true",
                    help="bucket the hierarchical gradient reduce "
                         "(reverse-layer order) so the scheduler can "
                         "overlap the per-bucket collectives with "
                         "surrounding compute (requires --dp-ici-size; "
                         "see docs/distributed.md)")
    ap.add_argument("--bucket-mb", type=float, default=4.0,
                    help="bucket size in MiB for --overlap-grad-sync "
                         "(the reference's message_size analog)")
    ap.add_argument("--num-experts", type=int, default=None,
                    help="Switch-MoE experts riding dp as the ep axis")
    ap.add_argument("--position-embedding", default="learned",
                    choices=["learned", "rope"],
                    help="rope = rotary (q, k) rotation, no position "
                         "table; any sequence length runs")
    ap.add_argument("--activation", default="gelu",
                    choices=["gelu", "swiglu"])
    ap.add_argument("--normalization", default="layernorm",
                    choices=["layernorm", "rmsnorm"])
    ap.add_argument("--clip-grad", type=float, default=None,
                    help="global-norm gradient clipping (mesh-aware)")
    ap.add_argument("--data", default=None,
                    help="apex_tpu.data token file (write_token_file); "
                         "synthetic stream when omitted")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--save-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10,
                    help="telemetry flush cadence: device scalars "
                         "(loss) resolve and print every N steps — the "
                         "ONLY per-step host sync knob (1 = the old "
                         "synchronous behaviour)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append structured step metrics + subsystem "
                         "events here (tools/metrics_report.py reads "
                         "it)")
    ap.add_argument("--trace-dir", default=None,
                    help="arm the on-demand trace trigger: touch "
                         "<trace-dir>/TRACE_REQUEST mid-run to capture "
                         "an xplane window (APEX_TPU_TRACE_STEPS "
                         "steps) without restarting")
    ap.add_argument("--watchdog-s", type=float, default=None,
                    help="stall watchdog deadline in seconds (dumps "
                         "all-thread stacks on heartbeat silence; "
                         "heartbeats mirror to "
                         "$APEX_TPU_HEARTBEAT_FILE for "
                         "resilience.watchdog.read_heartbeat)")
    args = ap.parse_args(argv)

    hier = args.dp_ici_size is not None
    any_zero = args.zero or args.zero3
    if args.zero and args.zero3:
        ap.error("--zero and --zero3 are one knob at two depths: "
                 "state sharding vs full parameter sharding — pick "
                 "one")
    if args.zero3 and args.num_experts:
        ap.error("--zero3 cannot shard data-axis-sharded expert "
                 "leaves (they have no replicated copy to re-shard); "
                 "use --zero for MoE")
    if args.grad_compression != "none" and not hier:
        ap.error("--grad-compression quantizes the DCN leg of the "
                 "hierarchical reduce: it requires --dp-ici-size")
    if args.overlap_grad_sync and not hier:
        ap.error("--overlap-grad-sync buckets the hierarchical data "
                 "sync: it requires --dp-ici-size")
    if args.overlap_grad_sync and any_zero:
        ap.error("--overlap-grad-sync applies to the DDP reduce; "
                 "--zero/--zero3 replace it with the sharded "
                 "optimizer's reduce-scatter")
    if args.fused_opt_tail and any_zero:
        ap.error("--fused-opt-tail packs the replicated FusedAdam "
                 "state; --zero/--zero3 already run the update on "
                 "one flat sharded buffer")
    if args.fused_opt_tail and (args.pp > 1 or args.tp > 1
                                or args.num_experts):
        ap.error("--fused-opt-tail needs replicated params: the "
                 "packed state buffers concatenate leaves across "
                 "bucket boundaries and cannot be sharded over "
                 "pp/tp/ep axes (see docs/optimizers.md) — drop the "
                 "flag or the model-parallel axes")
    bucket_bytes = int(args.bucket_mb * 1024 * 1024)
    if hier and args.num_experts:
        ap.error("--dp-ici-size is incompatible with --num-experts "
                 "(experts ride the dp axis, which the hierarchical "
                 "layout keeps at size 1)")
    if args.compress_ici_legs and args.grad_compression == "none":
        ap.error("--compress-ici-legs extends --grad-compression int8 "
                 "to the ICI legs: enable int8 first")
    comp = None
    if args.grad_compression != "none":
        from apex_tpu.ops.quantization import CompressionConfig

        comp = CompressionConfig(
            method=args.grad_compression,
            block_size=args.compression_block,
            rounding=args.compression_rounding,
            error_feedback=not args.no_error_feedback,
            ici_legs=args.compress_ici_legs,
        )
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=args.tp,
        pipeline_model_parallel_size_=args.pp,
        data_parallel_ici_size_=args.dp_ici_size,
    )
    data_axes = parallel_state.data_parallel_axis_names()
    dp = parallel_state.get_data_parallel_world_size()
    mp = amp.initialize(opt_level=args.opt_level)
    cfg = GPTConfig(
        vocab_size=args.vocab, num_layers=args.layers,
        hidden_size=args.hidden, num_attention_heads=args.heads,
        max_position_embeddings=args.seq, policy=mp.policy,
        position_embedding=args.position_embedding,
        activation=args.activation,
        normalization=args.normalization,
        num_experts=args.num_experts,
        moe_capacity_factor=2.0,  # read only when num_experts is set
    )
    model = GPTModel(cfg)
    pp_path = args.pp > 1
    specs = model.pipeline_param_specs() if pp_path else model.param_specs()
    if args.fused_opt_tail:
        # the packed buffers concatenate leaves across bucket
        # boundaries, so nothing is sharded over a model axis (checked
        # above).  The specs say so: a size-1 "tp" entry would still
        # type every packed bucket tp-varying
        specs = jax.tree.map(lambda _: P(), specs,
                             is_leaf=lambda x: isinstance(x, P))
    params = model.init(jax.random.PRNGKey(0))
    use_scaler = mp.policy.loss_scale is not None
    amp_state = mp.init()

    place = lambda t, sp: jax.device_put(
        t, jax.tree.map(lambda s: NamedSharding(mesh, s), sp,
                        is_leaf=lambda x: isinstance(x, P)))

    if any_zero:
        from apex_tpu.contrib.optimizers import (
            DistributedFusedAdam,
            reestablish_replicated,
        )

        # param_specs routes MoE expert leaves (dp-sharded as ep)
        # through the rank-local update instead of the flat RS/AG.
        # Hierarchical: RS rides ici, the 1/ici shard all-reduces
        # across dcn (int8-quantized when --grad-compression is set,
        # residual state inside the optimizer state).  --zero3
        # additionally shards the PARAMS: they live as the flat fp32
        # shard and are gathered per bucket on use inside the step
        # (int8 gather under --compress-ici-legs)
        opt = DistributedFusedAdam(
            lr=args.lr, param_specs=specs,
            axis_name=data_axes if hier else "dp",
            compression=comp,
            shard_params=args.zero3,
            bucket_bytes=bucket_bytes,
        )
        if args.zero3:
            opt.build_layout(params, mesh=mesh)
            shard_spec = opt.shard_spec(model_axes=("pp", "tp"))
            init_shards = jax.jit(jax.shard_map(
                opt.init_shards, mesh=mesh, in_specs=(specs,),
                out_specs=shard_spec))
            opt_specs = opt.state_specs(model_axes=("pp", "tp"))
            init_opt = jax.jit(jax.shard_map(
                opt.init, mesh=mesh, in_specs=(shard_spec,),
                out_specs=opt_specs))
        else:
            opt_specs = opt.state_specs(model_axes=("pp", "tp"))
            init_opt = jax.jit(jax.shard_map(
                opt.init, mesh=mesh, in_specs=(specs,),
                out_specs=opt_specs))
    else:
        # --fused-opt-tail: moments + masters live as packed bucket
        # buffers and the whole clip→adam→cast chain is one pass per
        # buffer (bit-identical at fp32 moments; see docs/optimizers.md)
        opt = FusedAdam(lr=args.lr,
                        master_weights=mp.policy.master_weights,
                        fused_tail=args.fused_opt_tail,
                        exp_avg_sq_dtype=jnp.dtype(args.exp_avg_sq_dtype))
        opt_state = opt.init(params)
        opt_specs = state_specs_like(specs, opt_state)

    # comm state for the compressed DDP reduce: error-feedback
    # residuals, and the step counter stochastic rounding derives its
    # per-step key from (ZeRO carries its own inside the optimizer
    # state)
    use_comm = (comp is not None and not any_zero
                and (comp.error_feedback
                     or comp.rounding == "stochastic"))
    if use_comm:
        from apex_tpu.parallel.distributed import (
            comm_state_specs,
            init_comm_state,
        )

        if args.overlap_grad_sync:
            # per-BUCKET residuals matching the bucketed reduce; the
            # plan must see the same leaf shapes/dtypes and bucket
            # size the in-step reduce derives its own plan from
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

    def train_step(params, opt_state, amp_state, comm_state,
                   tokens, targets):
        # --zero3: ``params`` is the flat fp32 shard; gather-on-use
        # rebuilds the model-dtype tree per bucket (tlm.param_gather
        # scopes inside), advancing the ag residual when the gather is
        # int8 + error feedback.  The replicated-typed invariant over
        # pp/tp is re-established for the pipeline/TP collectives.
        if args.zero3:
            weights, opt_state = opt.gather_params(params, opt_state)
            weights = reestablish_replicated(weights, specs)
        else:
            weights = params
        # tlm.* phase scopes: xprof segments the compiled step's
        # timeline by phase (fwd_bwd / grad_sync / optimizer) instead
        # of by mangled fusion names — see docs/observability.md
        with phase("fwd_bwd"):
            if pp_path:
                loss, grads = model.pipeline_1f1b_grads(
                    weights, tokens, targets, args.num_micro)
                if use_scaler:
                    # fp16 + pipeline: scale the already-computed grads
                    # so the scaler's overflow-skip + adjustment state
                    # machine runs (infs survive finite scaling).  This
                    # protects against overflow but NOT bwd underflow —
                    # the bf16 levels (the TPU default) are the
                    # recommended pipeline precision and need no scaler
                    # at all
                    s = amp_state.scaler_states[0].loss_scale
                    grads = jax.tree.map(
                        lambda g: g * s.astype(g.dtype), grads)
            else:
                def loss_fn(p):
                    loss = model.loss(p, tokens, targets)
                    return mp.scale_loss(amp_state, loss), loss

                # model.loss has averaged over dp: loss is dp-invariant
                grads, loss = jax.grad(loss_fn, has_aux=True)(weights)
        if not pp_path and not any_zero and not hier:
            # ZeRO skips this: its reduce-scatter is the reduction
            with phase("grad_sync"):
                grads = dp_mean_where_varying(grads, specs)
        if hier:
            # the dummy "dp" axis made every model-internal dp reduce a
            # no-op: the data-axis loss mean happens here instead
            loss = jax.lax.pmean(loss, data_axes)
        if use_scaler:
            # MoE: expert grads differ per dp rank, so the overflow
            # verdict must ALSO reach dp consensus or ranks would skip
            # steps independently and desync replicated params.
            # Hierarchical: grads are not data-synced until after the
            # unscale (below), so the verdict must span the data axes —
            # doubly so with compression, which scrambles infs
            axes = ("tp", "pp")
            if args.num_experts:
                axes += ("dp",)
            if hier:
                axes += data_axes
            grads, finite, amp_state = mp.unscale_and_adjust(
                amp_state, grads,
                finite_reduce=lambda f: model_parallel_all_finite(
                    f, axis_names=axes))
        else:
            finite = None
        new_comm = comm_state
        if hier and not any_zero:
            # data sync AFTER the unscale: the compressed reduce sees
            # true-magnitude grads (the error-feedback residual is then
            # consistent across dynamic loss-scale changes), RS rides
            # ici, only the 1/ici chunk crosses dcn (int8 + fp32
            # scales when compressed)
            from apex_tpu.parallel import all_reduce_gradients

            if use_comm:
                grads, new_comm = all_reduce_gradients(
                    grads, axis_name=data_axes, compression=comp,
                    comm_state=comm_state,
                    overlap_grad_sync=args.overlap_grad_sync,
                    bucket_bytes=bucket_bytes)
                if finite is not None:
                    # a skipped (overflowed) step must not absorb
                    # garbage into the residual
                    from apex_tpu.optimizers.base import tree_where

                    new_comm = tree_where(finite, new_comm, comm_state)
            else:
                grads = all_reduce_gradients(
                    grads, axis_name=data_axes, compression=comp,
                    overlap_grad_sync=args.overlap_grad_sync,
                    bucket_bytes=bucket_bytes)
        with phase("optimizer"):
            if args.clip_grad is not None:
                # AFTER unscale (clip sees true-magnitude grads),
                # BEFORE the optimizer; duplicate-aware over the mesh
                # (tp/pp shards + expert-dp leaves psum, replicated
                # leaves count once)
                grads, _ = clip_grad_norm(grads, specs, args.clip_grad)
            if args.zero3:
                # grads reduce-scatter straight into the shard; the
                # update runs there and NOTHING gathers back — the
                # next step's gather-on-use is the gather
                new_params, new_opt = opt.step(
                    opt_state, grads, params, grads_finite=finite)
            elif args.zero:
                # expert grads are optimizer-ready in BOTH paths here:
                # the pipeline's data_reduce applies the 1/n itself,
                # and the pp=1 path's model.loss pmeans the loss inside
                # the differentiated function (the all_to_all transpose
                # then delivers the final global-mean gradient) — so
                # the local path must not divide again
                new_params, new_opt = opt.step(
                    opt_state, grads, params, grads_finite=finite,
                    local_grads_prenormalized=True)
                new_params = reestablish_replicated(new_params, specs)
            else:
                new_params, new_opt = opt.step(
                    opt_state, grads, params, grads_finite=finite)
        return new_params, new_opt, amp_state, new_comm, loss

    amp_specs = jax.tree.map(lambda _: P(), amp_state)
    data_spec = P(data_axes if hier else "dp")
    # the threaded "params" are the flat shard under --zero3 — the
    # replicated tree never exists between steps
    store_spec = shard_spec if args.zero3 else specs
    _programs.own(train_step.__name__, layer="train step")
    step = jax.jit(
        jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(store_spec, opt_specs, amp_specs, comm_specs,
                      data_spec, data_spec),
            out_specs=(store_spec, opt_specs, amp_specs, comm_specs,
                       P()),
        ),
        donate_argnums=(0, 1),
    )

    n_params = sum(int(np.prod(jnp.shape(l)))
                   for l in jax.tree.leaves(params))
    placed = place(params, specs)
    if args.zero3:
        # the shards are the storage from here on: drop the replicated
        # init tree, or a full param copy stays pinned all run and the
        # ~world-fold persistent-bytes win never materializes
        placed = init_shards(placed)
        jax.block_until_ready(placed)
        del params
    start = 0
    ar = None
    restored = None
    if args.checkpoint_dir:
        ar = AutoResume(args.checkpoint_dir,
                        interval_steps=args.save_every,
                        install_sigterm_handler=True)
        restored, start = ar.resume()
        if restored is not None:
            # --zero3 checkpoints hold the flat shard buffer (1/world
            # the bytes of the replicated tree); resume at the same
            # data-parallel topology
            placed = place(restored["params"], store_spec)
            amp_state = mp.load_state_dict(restored["amp"])
            if use_comm and "comm" in restored:
                # resumed error-feedback residuals keep the
                # quantization compensation instead of re-zeroing it
                comm_state = restored["comm"]
            start += 1  # the saved step already ran
            print(f"resuming after step {start - 1}")
    # optimizer state AFTER the resume decision, so a restored run
    # never reverts to freshly-initialised masters
    if any_zero:
        opt_state = (place(restored["opt"], opt_specs)
                     if restored is not None and "opt" in restored
                     else init_opt(placed))
    else:
        opt_state = (place(restored["opt"], opt_specs)
                     if restored is not None and "opt" in restored
                     else place(opt_state, opt_specs))

    comm_state = place(comm_state, comm_specs)
    # on the mesh like everything the step returns, or the second call
    # sees differently-typed scaler state and compiles the step again
    amp_state = place(amp_state, amp_specs)
    global_batch = args.micro_batch * args.num_micro * dp
    pool = (file_batches(args.data, 8, global_batch, args.seq, args.vocab)
            if args.data else
            batches(np.random.default_rng(0), 8, global_batch,
                    args.seq, args.vocab))

    # telemetry: loss stays an unresolved device future between
    # flushes; tokens/s + MFU come from the same FLOP model bench.py /
    # tools/scale_mfu.py report, timed from AFTER the first step so the
    # XLA compile never pollutes ms/step
    stats = StepStats(
        tokens_per_step=global_batch * args.seq,
        flops_per_token=transformer_flops_per_token(
            n_params, args.layers, args.hidden, args.seq),
    )
    tlm = MetricsLogger(jsonl_path=args.metrics_jsonl,
                        flush_every=args.log_every, stats=stats,
                        run="gpt_pretrain")
    tlm.attach_events()  # checkpoint/comm/guard events join the stream
    trig = TraceTrigger(trace_dir=args.trace_dir) \
        if (args.trace_dir or os.environ.get("APEX_TPU_TRACE_DIR")) \
        else None
    wd = None
    if args.watchdog_s:
        from apex_tpu.resilience import Watchdog

        wd = Watchdog(deadline_s=args.watchdog_s).start()
    loss = jnp.float32(float("nan"))
    try:
        for i in range(start, args.steps):
            with tlm.timing("data"):
                tokens, targets = pool[i % len(pool)]
            placed, opt_state, amp_state, comm_state, loss = step(
                placed, opt_state, amp_state, comm_state, tokens, targets)
            if i == start:
                stats.begin(loss)  # blocks once: compile excluded
            else:
                stats.tick()
            tlm.log_scalars(i, loss=loss)  # async: resolves at cadence
            if trig is not None:
                trig.poll(i)
            if wd is not None:
                wd.beat(step=i)
            if ar is not None:
                # build the (expensive, device_get-ing) state dict only
                # on ticks maybe_save would actually write
                due = (i > 0 and i % args.save_every == 0) \
                    or ar.termination_requested() or i == args.steps - 1
                if due:
                    with tlm.timing("checkpoint"), phase("checkpoint"):
                        state = {"params": jax.device_get(placed),
                                 "opt": jax.device_get(opt_state),
                                 "amp": mp.state_dict(amp_state),
                                 "step": np.int64(i)}
                        if use_comm:
                            state["comm"] = jax.device_get(comm_state)
                        saved = ar.maybe_save(i, state,
                                              force=(i == args.steps - 1))
                    if saved and ar.termination_requested():
                        print("termination requested; checkpoint saved")
                        return {"loss": float(loss), "stopped_at": i}
        summary = stats.summary(loss)  # blocks on the final step
        tlm.flush()
        if summary.get("timed_steps"):
            line = (f"{summary['ms_per_step']:.1f} ms/step  "
                    f"{summary['tokens_per_sec']:,.0f} tokens/s")
            if "mfu" in summary:
                line += f"  mfu {summary['mfu']:.3f}"
            print(line)
        # beside the result: what a caller needs to go on from here
        # without rebuilding it (chip_smoke.py serves these params and
        # reads the compiled step's text) — the model, the jitted step
        # and arguments it can be lowered with (the live state; the
        # step donates its first two)
        return {"loss": float(loss), "params": placed,
                "summary": summary, "model": model, "step": step,
                "step_args": (placed, opt_state, amp_state, comm_state)
                + pool[0]}
    finally:
        if wd is not None:
            wd.stop()
        if trig is not None:
            trig.close()
        tlm.close()  # flushes, deregisters the event sink, closes fd


if __name__ == "__main__":
    main()
