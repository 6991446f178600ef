"""ImageNet-style ResNet trainer — the flagship integration example
(reference: examples/imagenet/main_amp.py:73-190: RN50 + amp O2 + DDP +
SyncBN + eval with prec@1/5 + checkpoint/resume + best-model tracking).

Feature-for-feature with the reference trainer, TPU-native:

- O2-analog mixed precision: bf16 compute inside the model, fp32 master
  weights in FusedSGD, BN statistics in fp32 synchronized over the "dp"
  mesh axis (the model's built-in SyncBN — reference's
  ``parallel.SyncBatchNorm`` + ``--sync_bn``);
- training epochs with running loss / prec@1 / prec@5 meters;
- a validation pass computing prec@1 / prec@5
  (reference: main_amp.py ``validate`` + ``accuracy``);
- checkpoint save every epoch via :mod:`apex_tpu.checkpoint` (manifest +
  flat blob through the C++ flatten), best-model tracking
  (``best.ckpt``), and ``--resume`` restoring params, optimizer,
  BN stats, epoch counter and best-prec@1 exactly
  (reference: main_amp.py checkpoint dict + ``--resume`` branch);
- ``--evaluate`` runs validation only;
- pluggable data: synthetic batches by default so the example runs
  anywhere; replace :func:`synthetic_batches` with a real input
  pipeline for actual training.

    python examples/imagenet_amp.py --depth 50 --batch-size 32 \
        --epochs 2 --steps-per-epoch 20 --checkpoint-dir /tmp/rn50
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu import checkpoint
from apex_tpu.models.resnet import ResNet, ResNetConfig
from apex_tpu.optimizers import FusedSGD
from apex_tpu.telemetry.metrics import MetricsLogger, StepStats
from apex_tpu.transformer import parallel_state


def synthetic_pool(seed, n_batches, global_batch, image_size, num_classes):
    """Deterministic synthetic dataset: ``n_batches`` pre-generated
    ``(images, labels)`` pairs — the pluggable data source.

    Pre-generating keeps host-side RNG out of the timed training loop
    (the device step, not numpy, is what the img/s figure measures) and
    gives validation a FIXED set so prec@1 is comparable across epochs,
    like the reference's val loader.  Swap for a real pipeline yielding
    ``images: (global_batch, H, W, 3) float32`` NHWC and
    ``labels: (global_batch,) int32``."""
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(n_batches):
        images = jnp.asarray(rng.normal(
            size=(global_batch, image_size, image_size, 3)
        ).astype(np.float32))
        labels = jnp.asarray(
            rng.integers(0, num_classes, (global_batch,)), jnp.int32
        )
        pool.append((images, labels))
    return pool


def _topk_correct(logits, labels):
    """(#top1-correct, #top5-correct) on the local shard — psum'd by the
    caller (reference: main_amp.py ``accuracy(output, target, topk=(1,5))``)."""
    top5 = jax.lax.top_k(logits, 5)[1]
    hit = top5 == labels[:, None]
    return (
        jnp.sum(hit[:, 0].astype(jnp.float32)),
        jnp.sum(jnp.any(hit, axis=1).astype(jnp.float32)),
    )


def build_steps(model, opt, num_classes, mesh, param_tree, opt_tree,
                stats_tree):
    """Compile the train and eval steps once; both return meter updates."""
    to_spec = lambda tree: jax.tree.map(lambda _: P(), tree)
    pspec, ospec, sspec = (to_spec(param_tree), to_spec(opt_tree),
                           to_spec(stats_tree))

    def train_step(params, opt_state, bn_stats, images, labels):
        def loss_fn(p, stats):
            logits, new_stats = model.apply(p, stats, images, training=True)
            one_hot = jax.nn.one_hot(labels, num_classes)
            loss = -jnp.mean(
                jnp.sum(jax.nn.log_softmax(logits) * one_hot, axis=-1)
            )
            return loss, (new_stats, logits)

        (loss, (new_stats, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params, bn_stats)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
        # running BN stats averaged over dp (activations were already
        # SyncBN-normalized inside apply; this keeps the saved stats
        # identical on every rank)
        new_stats = jax.tree.map(
            lambda s: jax.lax.pmean(s, "dp"), new_stats
        )
        new_params, new_opt = opt.step(opt_state, grads, params)
        c1, c5 = _topk_correct(logits, labels)
        n = jnp.float32(labels.shape[0])
        meters = jax.lax.psum(jnp.stack([c1, c5, n]), "dp")
        return (new_params, new_opt, new_stats,
                jax.lax.pmean(loss, "dp"), meters)

    def eval_step(params, bn_stats, images, labels):
        logits, _ = model.apply(params, bn_stats, images, training=False)
        one_hot = jax.nn.one_hot(labels, num_classes)
        loss = -jnp.mean(
            jnp.sum(jax.nn.log_softmax(logits) * one_hot, axis=-1)
        )
        c1, c5 = _topk_correct(logits, labels)
        n = jnp.float32(labels.shape[0])
        return (jax.lax.pmean(loss, "dp"),
                jax.lax.psum(jnp.stack([c1, c5, n]), "dp"))

    train = jax.jit(
        jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(pspec, ospec, sspec, P("dp"), P("dp")),
            out_specs=(pspec, ospec, sspec, P(), P()),
        ),
        donate_argnums=(0, 1, 2),
    )
    evaluate = jax.jit(jax.shard_map(
        eval_step, mesh=mesh,
        in_specs=(pspec, sspec, P("dp"), P("dp")),
        out_specs=(P(), P()),
    ))
    return train, evaluate


def validate(evaluate, params, bn_stats, val_pool):
    """Full pass over the fixed val set → (mean loss, prec@1, prec@5)
    in percent (reference: main_amp.py ``validate``)."""
    tot = np.zeros(3)
    losses = []
    for images, labels in val_pool:
        loss, meters = evaluate(params, bn_stats, images, labels)
        losses.append(float(loss))
        tot += np.asarray(meters)
    c1, c5, n = tot
    return float(np.mean(losses)), 100.0 * c1 / n, 100.0 * c5 / n


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=32,
                    help="per-device batch")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--steps-per-epoch", type=int, default=20)
    ap.add_argument("--eval-steps", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="save per-epoch checkpoints + best.ckpt here")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from "
                         "--checkpoint-dir before training")
    ap.add_argument("--evaluate", action="store_true",
                    help="validation only (with --resume to score a "
                         "saved model)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append structured step metrics + checkpoint "
                         "events here")
    args = ap.parse_args(argv)

    mesh = parallel_state.initialize_model_parallel()
    dp = mesh.shape["dp"]
    model = ResNet(ResNetConfig(depth=args.depth,
                                num_classes=args.num_classes))
    opt = FusedSGD(lr=args.lr, momentum=0.9, weight_decay=1e-4,
                   master_weights=True)

    params, bn_stats = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    start_epoch, best_prec1 = 0, 0.0

    if args.resume:
        if not args.checkpoint_dir:
            raise SystemExit("--resume needs --checkpoint-dir")
        last = checkpoint.latest_step(args.checkpoint_dir)
        if last is None:
            print(f"no checkpoint under {args.checkpoint_dir}; "
                  "starting fresh")
        else:
            target = {"params": params, "opt_state": opt_state,
                      "bn_stats": bn_stats,
                      "epoch": np.int64(0), "best_prec1": np.float64(0.0)}
            state = checkpoint.restore_step(
                args.checkpoint_dir, target=target, step=last
            )
            params, opt_state, bn_stats = (
                state["params"], state["opt_state"], state["bn_stats"]
            )
            start_epoch = int(state["epoch"]) + 1
            best_prec1 = float(state["best_prec1"])
            print(f"resumed epoch {int(state['epoch'])} "
                  f"(best prec@1 {best_prec1:.2f}) from "
                  f"{args.checkpoint_dir}")

    train, evaluate = build_steps(
        model, opt, args.num_classes, mesh, params, opt_state, bn_stats
    )
    global_batch = args.batch_size * dp
    # small cycled pool for training, fixed set for validation (host
    # RNG stays out of the timed loop; val scores are comparable)
    train_pool = synthetic_pool(
        0, min(args.steps_per_epoch, 8), global_batch, args.image_size,
        args.num_classes,
    )
    val_pool = synthetic_pool(
        1, args.eval_steps, global_batch, args.image_size,
        args.num_classes,
    )

    if args.evaluate:
        loss, p1, p5 = validate(evaluate, params, bn_stats, val_pool)
        print(f"eval: loss {loss:.3f}  prec@1 {p1:.2f}  prec@5 {p5:.2f}")
        return {"prec1": p1, "prec5": p5}

    # telemetry: per-step loss/meters stay device futures; ONE batched
    # device_get resolves the whole epoch (the old loop synced twice
    # per step: float(loss) + np.asarray(meters)).  ms/step excludes
    # the first step of each epoch (only epoch 0's includes a compile,
    # but the exclusion is uniform — the same timing contract as the
    # gpt/bert/t5 trainers)
    stats = StepStats(tokens_per_step=global_batch, unit="img")
    # close() (the with-exit) deregisters the logger from the event
    # bus, so an exception mid-epoch cannot leak the sink or the fd
    with MetricsLogger(jsonl_path=args.metrics_jsonl, console=False,
                       flush_every=max(args.steps_per_epoch, 1),
                       run="imagenet_amp").attach_events() as tlm:
        return _train_epochs(
            args, tlm, stats, train, evaluate, train_pool, val_pool,
            params, opt_state, bn_stats, start_epoch, best_prec1,
            global_batch)


def _train_epochs(args, tlm, stats, train, evaluate, train_pool,
                  val_pool, params, opt_state, bn_stats, start_epoch,
                  best_prec1, global_batch):
    for epoch in range(start_epoch, args.epochs):
        held = []  # (loss, meters) device pairs, resolved at epoch end
        for i in range(args.steps_per_epoch):
            images, labels = train_pool[i % len(train_pool)]
            params, opt_state, bn_stats, loss, meters = train(
                params, opt_state, bn_stats, images, labels
            )
            held.append((loss, meters))
            if i == 0:
                stats.begin((loss, meters))  # blocks once per epoch
            else:
                stats.tick()
            tlm.log_scalars(epoch * args.steps_per_epoch + i, loss=loss)
        summary = stats.summary(held[-1] if held else None)
        resolved = jax.device_get(held)  # one transfer for the epoch
        losses = [float(l) for l, _ in resolved]
        tot = np.sum([np.asarray(m) for _, m in resolved], axis=0) \
            if resolved else np.zeros(3)
        ips = summary.get("tokens_per_sec", float("nan"))
        c1, c5, n = tot
        print(f"epoch {epoch}: loss {np.mean(losses):.3f}  "
              f"prec@1 {100 * c1 / n:.2f}  prec@5 {100 * c5 / n:.2f}  "
              f"{ips:,.1f} img/s ({ips / max(jax.device_count(), 1):,.1f}"
              f"/chip)")

        val_loss, p1, p5 = validate(evaluate, params, bn_stats, val_pool)
        is_best = p1 > best_prec1
        best_prec1 = max(best_prec1, p1)
        print(f"  val: loss {val_loss:.3f}  prec@1 {p1:.2f}  "
              f"prec@5 {p5:.2f}  best {best_prec1:.2f}"
              f"{'  *' if is_best else ''}")

        if args.checkpoint_dir:
            state = {"params": params, "opt_state": opt_state,
                     "bn_stats": bn_stats, "epoch": epoch,
                     "best_prec1": best_prec1}
            path = checkpoint.save_step(args.checkpoint_dir, epoch, state)
            if is_best:
                checkpoint.save(
                    os.path.join(args.checkpoint_dir, "best.ckpt"), state
                )
            print(f"  saved {path}" + ("  (best)" if is_best else ""))

    return {"params": params, "opt_state": opt_state,
            "bn_stats": bn_stats, "best_prec1": best_prec1}


if __name__ == "__main__":
    main()
