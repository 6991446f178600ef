"""Per-device memory audit: compile the train step and prove the
live-bytes math — the tool that gates the ZeRO-3 claim.

OOM cannot be demonstrated on a CPU host (the virtual devices share
one heap), so the "replicated DDP cannot hold the h≥4096-class model
in 16 GB HBM" claim is proven STRUCTURALLY, the same way
``tools/comm_audit.py`` proves wire bytes: compile the full training
step (no execution — parameters enter as ``ShapeDtypeStruct``\\ s, so
a ≥1B-param model audits in seconds) and read XLA's buffer-assignment
numbers from ``Compiled.memory_analysis()``:

- ``argument_bytes`` — the per-device bytes of everything the step is
  *handed*: model params + fp32 masters + both moments for replicated
  DDP; the 1/world fp32 shard + 1/world moments for ZeRO-3.  This is
  the persistent training state and it is exact.
- ``temp_bytes`` — XLA's temp allocation (liveness-packed peak of the
  intermediates): activations, gradients and — under ZeRO-3 — the
  transient gathered weights.
- ``peak_bytes`` — ``argument + output + temp − alias`` (donated
  outputs alias their arguments), the per-device high-water mark the
  HBM verdict uses.

``--compare`` compiles replicated-DDP and ZeRO-3 at the same shape and
prints them side by side with the ratio and a per-device HBM verdict;
the multichip dryrun's twelfth config wires this into
``MEMORY_AUDIT.json`` and gates replicated > HBM ≥ zero3 at the
≥1B-param flagship shape.  ``--train-steps N`` additionally
materializes the ZeRO-3 config and runs N real optimizer steps (the
"trains where DDP cannot" half of the gate — slow on a CPU host, so
off by default).

Run on the 8-device virtual mesh (no TPU needed):

    python tools/memory_audit.py --compare            # flagship ≥1B shape
    python tools/memory_audit.py --compare --layers 2 --hidden 256
    python tools/memory_audit.py --train-steps 8 --layers 2 --hidden 256

``--serve`` is the SERVING analog of the train audit: per-device
decode-path bytes (weight pool + KV pool + decode activations) for a
ladder of model tiers at every weight width — fp32 / bf16 / int8 /
int4 pools (``quantize_gpt_weights``) — with an HBM verdict naming the
largest tier that fits at each width.  Pure shape math (eval_shape of
the actual pool builders, no compile, no materialization), so the 20B+
tiers audit in milliseconds:

    python tools/memory_audit.py --serve              # writes MEMORY_AUDIT_SERVE.json
    python tools/memory_audit.py --serve --context 2048 --max-seqs 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _force_virtual_devices(n: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


#: The ≥1B-param flagship audit shape: h=2048 x 20 layers ≈ 1.07B
#: params — the smallest config that proves the "replicated DDP
#: exceeds 16 GB/device, ZeRO-3 fits" claim (h≥4096 scales the same
#: math up).  seq/batch are tiny: the claim is about STATE bytes, and
#: small activations keep the CPU compile fast.
FLAGSHIP_1B = dict(vocab=32768, layers=20, hidden=2048, heads=16,
                   seq=8, batch=8)

DEFAULT_HBM_GB = 16.0  # v5e per-chip HBM


def _mesh():
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    return parallel_state.initialize_model_parallel()


def _model(vocab, layers, hidden, heads, seq):
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTModel

    return GPTModel(GPTConfig(
        vocab_size=vocab, num_layers=layers, hidden_size=hidden,
        num_attention_heads=heads, max_position_embeddings=seq,
        compute_dtype=jnp.float32, remat=False, attention_impl="xla",
    ))


def _param_template(model):
    """ShapeDtypeStruct tree of the model params — no materialization,
    so a ≥1B-param model audits without 4 GB of host allocations."""
    import jax

    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def _n_params(tpl) -> int:
    import jax
    import numpy as np

    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(tpl)))


def _per_device_arg_bytes(avals, in_specs, mesh) -> int:
    """Exact per-device bytes of the step's arguments, from the avals
    and their PartitionSpecs: a replicated leaf costs its FULL size on
    every device, a sharded one 1/extent — the spec-aware sum a naive
    total//device_count gets wrong for replicated DDP state."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    total = 0
    for aval_tree, spec_tree in zip(avals, in_specs):
        leaves, treedef = jax.tree_util.tree_flatten(aval_tree)
        if isinstance(spec_tree, P):
            specs = [spec_tree] * len(leaves)
        else:
            specs = treedef.flatten_up_to(spec_tree)
        for leaf, spec in zip(leaves, specs):
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            denom = 1
            if spec is not None:
                for entry in spec:
                    if entry is None:
                        continue
                    names = (entry if isinstance(entry, tuple)
                             else (entry,))
                    for ax in names:
                        denom *= mesh.shape[ax]
            total += (n // max(denom, 1)) * np.dtype(leaf.dtype).itemsize
    return total


def build_step(mode, mesh, model, batch=8, bucket_mb=4.0):
    """Compile-ready ``(jitted, example_avals, arg_bytes_per_device)``
    for one train step.

    ``mode``: ``"ddp"`` — replicated params, FusedAdam with fp32
    masters (the seed path ZeRO-3 replaces); ``"zero3"`` — gather-on-
    use sharded params + sharded update.  Both donate their state so
    the peak model reflects in-place training."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P


    tpl = _param_template(model)
    specs = model.param_specs()
    seq = model.config.max_position_embeddings
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    if mode == "ddp":
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.transformer.tensor_parallel.layers import (
            state_specs_like,
        )

        opt = FusedAdam(lr=1e-2, master_weights=True)
        st_tpl = jax.eval_shape(opt.init, tpl)
        st_specs = state_specs_like(specs, st_tpl)

        def train(p, s, tok_, tgt_):
            loss, grads = jax.value_and_grad(model.loss)(p, tok_, tgt_)
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, "dp"), grads)
            p, s = opt.step(s, grads, p)
            return p, s, loss

        in_specs = (specs, st_specs, P("dp"), P("dp"))
        jitted = jax.jit(jax.shard_map(
            train, mesh=mesh,
            in_specs=in_specs,
            out_specs=(specs, st_specs, P()),
        ), donate_argnums=(0, 1))
        avals = (tpl, st_tpl, tok, tok)
        return jitted, avals, _per_device_arg_bytes(avals, in_specs,
                                                    mesh)

    from apex_tpu.contrib.optimizers import DistributedFusedAdam

    opt = DistributedFusedAdam(
        lr=1e-2, shard_params=True,
        bucket_bytes=int(bucket_mb * 1024 * 1024))
    layout = opt.build_layout(tpl, mesh=mesh)
    world = mesh.shape["dp"]
    sspec, st_specs = opt.shard_spec(), opt.state_specs()
    shards_g = jax.ShapeDtypeStruct(
        (world * layout.shard_size,), jnp.float32)
    st_g = {
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "exp_avg": shards_g, "exp_avg_sq": shards_g,
    }

    def train(sh, s, tok_, tgt_):
        p, s = opt.gather_params(sh, s)
        loss, grads = jax.value_and_grad(model.loss)(p, tok_, tgt_)
        sh, s = opt.step(s, grads, sh)
        return sh, s, loss

    in_specs = (sspec, st_specs, P("dp"), P("dp"))
    jitted = jax.jit(jax.shard_map(
        train, mesh=mesh,
        in_specs=in_specs,
        out_specs=(sspec, st_specs, P()),
    ), donate_argnums=(0, 1))
    avals = (shards_g, st_g, tok, tok)
    return jitted, avals, _per_device_arg_bytes(avals, in_specs, mesh)


def measure(jitted, avals, arg_exact=None) -> dict:
    """Compile and read per-device bytes from the buffer assignment;
    falls back to the spec-aware host-computed ``arg_exact`` (from
    :func:`_per_device_arg_bytes`) when the backend exposes no
    ``memory_analysis`` — every other field is then None, which the
    dryrun gate treats as a loud failure, not a pass."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*avals).compile()
    compile_s = time.perf_counter() - t0
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is None:
        # cost-analysis fallback: no liveness packing, so only the
        # (exact) argument bytes are trustworthy
        out = {"argument_bytes": arg_exact, "output_bytes": None,
               "temp_bytes": None, "alias_bytes": None,
               "peak_bytes": None, "source": "cost_analysis"}
    else:
        arg = int(ma.argument_size_in_bytes)
        outb = int(ma.output_size_in_bytes)
        temp = int(ma.temp_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        out = {
            "argument_bytes": arg,
            "output_bytes": outb,
            "temp_bytes": temp,
            "alias_bytes": alias,
            # arguments + outputs live across the program, temps are
            # the packed peak of everything else; donated outputs
            # alias arguments and must not double-count
            "peak_bytes": arg + outb + temp - alias,
            "source": "memory_analysis",
        }
    out["compile_s"] = round(compile_s, 2)
    return out


def run_memory_audit(vocab=None, layers=None, hidden=None, heads=None,
                     seq=None, batch=None, bucket_mb=4.0,
                     hbm_gb=DEFAULT_HBM_GB) -> dict:
    """The --compare document: replicated-DDP vs ZeRO-3 per-device
    bytes at one shape, with the ratio and the per-device HBM verdict
    the dryrun gates on."""
    cfg = dict(FLAGSHIP_1B)
    for k, v in dict(vocab=vocab, layers=layers, hidden=hidden,
                     heads=heads, seq=seq, batch=batch).items():
        if v is not None:
            cfg[k] = v
    mesh = _mesh()
    model = _model(cfg["vocab"], cfg["layers"], cfg["hidden"],
                   cfg["heads"], cfg["seq"])
    n_params = _n_params(_param_template(model))
    results = {}
    for mode in ("ddp", "zero3"):
        jitted, avals, arg_bytes = build_step(
            mode, mesh, model, batch=cfg["batch"], bucket_mb=bucket_mb)
        results[mode] = measure(jitted, avals, arg_bytes)
    hbm = hbm_gb * 1e9
    ddp_peak = results["ddp"]["peak_bytes"]
    z3_peak = results["zero3"]["peak_bytes"]
    doc = {
        "metric": "per_device_peak_bytes_ratio",
        "value": (round(ddp_peak / z3_peak, 2)
                  if ddp_peak and z3_peak else None),
        "unit": "x fewer per-device peak bytes (zero3 vs replicated "
                "ddp)",
        "config": cfg,
        "n_params": n_params,
        "world": int(mesh.shape["dp"]),
        "hbm_limit_bytes": int(hbm),
        "replicated_ddp": results["ddp"],
        "zero3": results["zero3"],
        "replicated_exceeds_hbm": (
            bool(ddp_peak > hbm) if ddp_peak else None),
        "zero3_fits_hbm": (bool(z3_peak < hbm) if z3_peak else None),
    }
    return doc


def train_zero3(vocab=None, layers=None, hidden=None, heads=None,
                seq=None, batch=None, steps=8, bucket_mb=4.0,
                lr=1e-4) -> dict:
    """Materialize the config and run ``steps`` real ZeRO-3 optimizer
    steps on the live mesh — the "a ≥1B-param GPT *trains* where
    replicated DDP cannot" half of the dryrun gate.  Memory-frugal by
    construction: the replicated init tree is dropped as soon as the
    shards are built, so the host never holds params + masters +
    moments the way the DDP path would."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.contrib.optimizers import DistributedFusedAdam

    cfg = dict(FLAGSHIP_1B)
    for k, v in dict(vocab=vocab, layers=layers, hidden=hidden,
                     heads=heads, seq=seq, batch=batch).items():
        if v is not None:
            cfg[k] = v
    mesh = _mesh()
    model = _model(cfg["vocab"], cfg["layers"], cfg["hidden"],
                   cfg["heads"], cfg["seq"])
    n_params = _n_params(_param_template(model))
    opt = DistributedFusedAdam(
        lr=lr, shard_params=True,
        bucket_bytes=int(bucket_mb * 1024 * 1024))
    opt.build_layout(_param_template(model), mesh=mesh)
    specs = model.param_specs()
    sspec, st_specs = opt.shard_spec(), opt.state_specs()
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(0))
    place = lambda t, sp: jax.device_put(
        t, jax.tree.map(lambda s: NamedSharding(mesh, s), sp,
                        is_leaf=lambda x: isinstance(x, P)))
    params = place(params, specs)
    shards = jax.jit(jax.shard_map(
        opt.init_shards, mesh=mesh, in_specs=(specs,),
        out_specs=sspec))(params)
    jax.block_until_ready(shards)
    del params  # the replicated tree is gone: shards are the storage
    state = jax.jit(jax.shard_map(
        opt.init, mesh=mesh, in_specs=(sspec,),
        out_specs=st_specs))(shards)
    init_s = time.perf_counter() - t0

    def train(sh, s, tok_, tgt_):
        p, s = opt.gather_params(sh, s)
        loss, grads = jax.value_and_grad(model.loss)(p, tok_, tgt_)
        sh, s = opt.step(s, grads, sh)
        return sh, s, loss

    step = jax.jit(jax.shard_map(
        train, mesh=mesh,
        in_specs=(sspec, st_specs, P("dp"), P("dp")),
        out_specs=(sspec, st_specs, P()),
    ), donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg["vocab"], (cfg["batch"], cfg["seq"])),
        jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        shards, state, loss = step(shards, state, tokens, targets)
        losses.append(float(loss))
        print(f"  zero3 step {i}: loss {losses[-1]:.4f} "
              f"({time.perf_counter() - t0:.1f}s elapsed)",
              flush=True)
    wall = time.perf_counter() - t0
    return {
        "config": cfg,
        "n_params": n_params,
        "steps": steps,
        "losses": [round(x, 5) for x in losses],
        "finite": bool(np.all(np.isfinite(losses))),
        "loss_decreased": bool(losses[-1] < losses[0]),
        "init_s": round(init_s, 1),
        "wall_s": round(wall, 1),
        "ms_per_step": round(wall / steps * 1e3, 1),
    }


#: The serving tier ladder (all head_dim=128, gelu MLP): chosen so the
#: 16 GB verdict lands one width apart per tier — fp32 carries the 3B,
#: bf16 the 8B, int8 the 13B and int4 the 30B class.  The 13B/30B rows
#: are the quantization claim: those tiers fit ONLY quantized.  The
#: 70B row is the tensor-parallel claim: it exceeds 16 GB at EVERY
#: width single-chip (int4 alone is ~36 GB of pool) and fits only
#: when the quantized pool and head-sharded KV pool are split over a
#: tp group — per-shard verdicts in the per-width ``tp`` sub-rows.
SERVE_TIERS = (
    ("1B", dict(vocab=32768, layers=20, hidden=2048, heads=16)),
    ("3B", dict(vocab=32768, layers=32, hidden=2560, heads=20)),
    ("8B", dict(vocab=32768, layers=32, hidden=4096, heads=32)),
    ("13B", dict(vocab=32768, layers=40, hidden=5120, heads=40)),
    ("30B", dict(vocab=32768, layers=44, hidden=6144, heads=48)),
    ("70B", dict(vocab=32768, layers=80, hidden=8192, heads=64)),
)

WEIGHT_WIDTHS = ("fp32", "bf16", "int8", "int4")

#: tp degrees audited by default — matches the decode_fns warmup grid.
SERVE_TP_DEGREES = (2, 4)


def _tree_bytes(tpl) -> int:
    import jax
    import numpy as np

    return int(sum(
        (int(np.prod(l.shape)) if l.shape else 1)
        * np.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tpl)))


def _serve_pool_tree(model, width, block=128, tp=1):
    """``eval_shape`` tree of the weight pool at ``width`` — from the
    ACTUAL pool builder (:func:`quantize_gpt_weights`), so scales,
    packing and the full-precision embedding/norm leaves are counted
    as built, not estimated.  ``tp`` is threaded through so the int4
    per-shard packing layout validates the same divisibility rules the
    serving path enforces."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import (
        QUANTIZED_WEIGHT_LEAVES, quantize_gpt_weights,
    )

    tpl = _param_template(model)
    if width == "fp32":
        return tpl
    if width == "bf16":
        def cast(p):
            layers = dict(p["layers"])
            for name in QUANTIZED_WEIGHT_LEAVES:
                if name in layers:
                    leaf = dict(layers[name])
                    leaf["weight"] = leaf["weight"].astype(jnp.bfloat16)
                    layers[name] = leaf
            return {**p, "layers": layers}

        return jax.eval_shape(cast, tpl)
    return jax.eval_shape(
        lambda p: quantize_gpt_weights(p, width, block, tp=tp), tpl)


def _serve_weight_pool_bytes(model, width, block=128) -> int:
    """Whole-pool bytes at ``width`` — what a dp-replicated (tp=1)
    device holds."""
    return _tree_bytes(_serve_pool_tree(model, width, block))


def _serve_pool_specs(model, width, pool):
    """Partition specs matching ``pool``'s pytree — the same specs
    :meth:`GPTModel.decode_fns` shards the served pool with (column
    leaves split the stacked output dim, row leaves the contraction
    dim, the vocab-parallel embedding its vocab rows; norms and row
    biases replicated)."""
    from apex_tpu.models.gpt import _quantized_layer_specs

    specs = model.param_specs()
    if width in ("int8", "int4"):
        specs["layers"] = _quantized_layer_specs(
            specs["layers"], pool["layers"], "tp")
    return specs


def _serve_per_shard_bytes(pool, specs, tp) -> int:
    """Bytes ONE tp shard holds of ``pool`` under ``specs``: each
    leaf's bytes divided by ``tp`` per sharded mesh axis in its spec
    (replicated leaves count in full).  Mirrors gpt.py's
    ``_per_chip_param_bytes`` but works on ``eval_shape`` trees (no
    ``nbytes`` on ShapeDtypeStruct) and needs no live mesh."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    def denom(spec):
        d = 1
        for entry in tuple(spec):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            d *= tp ** len(names)
        return d

    p_leaves = jax.tree.leaves(pool)
    s_leaves = jax.tree.leaves(specs,
                               is_leaf=lambda t: isinstance(t, P))
    if len(p_leaves) != len(s_leaves):
        raise ValueError(
            f"pool/spec tree mismatch: {len(p_leaves)} pool leaves "
            f"vs {len(s_leaves)} specs")
    return int(sum(
        (int(np.prod(x.shape)) if x.shape else 1)
        * np.dtype(x.dtype).itemsize // denom(s)
        for x, s in zip(p_leaves, s_leaves)))


def _serve_kv_pool_bytes(layers, heads, head_dim, *, max_seqs,
                         context, page_size, kv_dtype) -> int:
    """Exact paged-KV-pool bytes for the serving scenario, from
    ``eval_shape`` of :func:`init_pools` (int8 pools carry their
    per-block scales — counted, not approximated)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving.kv_cache import KVCacheConfig, init_pools

    pages_per_seq = -(-context // page_size)
    cfg = KVCacheConfig(
        num_layers=layers, num_heads=heads, head_dim=head_dim,
        num_pages=1 + max_seqs * pages_per_seq, page_size=page_size,
        max_seqs=max_seqs, pages_per_seq=pages_per_seq,
        dtype=jnp.float32, kv_dtype=kv_dtype)
    return _tree_bytes(jax.eval_shape(lambda: init_pools(cfg)))


def run_serve_audit(hbm_gb=DEFAULT_HBM_GB, max_seqs=4, context=1024,
                    page_size=64, block=128,
                    tp=SERVE_TP_DEGREES, draft_tier="1B") -> dict:
    """The --serve document: per-device decode-path bytes (weight pool
    + KV pool + decode activations) for every tier x weight width,
    and the largest tier that fits per width.  KV rides int8 (the
    shipping default since the paged-cache PR) with the fp32 pool
    bytes reported alongside; activations are a structural estimate
    (a handful of (max_seqs, ffn) rows plus the logits row — decode
    activations are microscopic next to the pools).

    Each width row additionally carries per-shard verdicts at every
    tensor-parallel degree in ``tp``: the weight pool divides by the
    decode_fns partition specs (quantized scales shard with their
    blocks), the KV pool head-shards, and a combo that is indivisible
    under the int4 per-shard packing rules reports ``fits_hbm: null``
    with the builder's own error as the note.  Tiers that fit NO width
    single-chip but fit some (width, tp) shard land in
    ``fits_only_tensor_parallel`` — the 70B row is the headline.

    ``draft_tier`` (a tier name, default "1B"; None disables) audits
    model-based speculation co-residency: the draft model's int4
    weight pool + its own int8 paged-KV slice (the
    ``ModelDraftSource`` serving state) are priced ONCE and added to
    every target width row as a ``with_draft`` verdict — the draft is
    replicated per tp shard (it is tiny and drafts on one chip), so
    tp sub-rows add the full draft bytes."""
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTModel

    hbm = hbm_gb * 1e9
    tiers = []
    largest_fit = {w: None for w in WEIGHT_WIDTHS}
    draft = None
    largest_fit_draft = {w: None for w in WEIGHT_WIDTHS}
    if draft_tier is not None:
        dshape = dict(SERVE_TIERS)[draft_tier]
        dmodel = GPTModel(GPTConfig(
            vocab_size=dshape["vocab"], num_layers=dshape["layers"],
            hidden_size=dshape["hidden"],
            num_attention_heads=dshape["heads"],
            max_position_embeddings=context,
            position_embedding="rope", compute_dtype=jnp.float32,
            remat=False, attention_impl="xla",
        ))
        draft = {
            "tier": draft_tier,
            "weight_width": "int4",
            "weight_pool_bytes": _serve_weight_pool_bytes(
                dmodel, "int4", block),
            "kv_pool_bytes": _serve_kv_pool_bytes(
                dshape["layers"], dshape["heads"],
                dshape["hidden"] // dshape["heads"],
                max_seqs=max_seqs, context=context,
                page_size=page_size, kv_dtype=jnp.int8),
        }
        draft["total_bytes"] = (draft["weight_pool_bytes"]
                                + draft["kv_pool_bytes"])
    for name, shape in SERVE_TIERS:
        head_dim = shape["hidden"] // shape["heads"]
        model = GPTModel(GPTConfig(
            vocab_size=shape["vocab"], num_layers=shape["layers"],
            hidden_size=shape["hidden"],
            num_attention_heads=shape["heads"],
            max_position_embeddings=context,
            position_embedding="rope", compute_dtype=jnp.float32,
            remat=False, attention_impl="xla",
        ))
        n_params = _n_params(_param_template(model))
        kv = {
            "fp32": _serve_kv_pool_bytes(
                shape["layers"], shape["heads"], head_dim,
                max_seqs=max_seqs, context=context,
                page_size=page_size, kv_dtype=None),
            "int8": _serve_kv_pool_bytes(
                shape["layers"], shape["heads"], head_dim,
                max_seqs=max_seqs, context=context,
                page_size=page_size, kv_dtype=jnp.int8),
        }
        act = int(max_seqs * (4 * shape["hidden"] * 4 * 4
                              + shape["vocab"] * 4))
        row = {"tier": name, "shape": dict(shape),
               "n_params": n_params, "kv_pool_bytes": kv,
               "activations_bytes": act, "widths": {}}
        for w in WEIGHT_WIDTHS:
            wp = _serve_weight_pool_bytes(model, w, block)
            total = wp + kv["int8"] + act
            fits = total < hbm
            row["widths"][w] = {
                "weight_pool_bytes": wp,
                "total_bytes": total,
                "fits_hbm": bool(fits),
            }
            if fits:
                largest_fit[w] = name     # tiers ascend in size
            if draft is not None:
                dtot = total + draft["total_bytes"]
                row["widths"][w]["with_draft"] = {
                    "total_bytes": dtot,
                    "fits_hbm": bool(dtot < hbm),
                }
                if dtot < hbm:
                    largest_fit_draft[w] = name
            tp_rows = {}
            for t in tp or ():
                if shape["heads"] % t:
                    tp_rows[str(t)] = {
                        "fits_hbm": None,
                        "note": f"{shape['heads']} heads do not "
                                f"divide tp={t}"}
                    continue
                try:
                    pool = _serve_pool_tree(model, w, block, tp=t)
                except ValueError as e:
                    tp_rows[str(t)] = {"fits_hbm": None,
                                       "note": str(e)}
                    continue
                specs = _serve_pool_specs(model, w, pool)
                wps = _serve_per_shard_bytes(pool, specs, t)
                kvs = kv["int8"] // t         # head-sharded pool
                totals = wps + kvs + act
                tp_rows[str(t)] = {
                    "per_shard_weight_pool_bytes": wps,
                    "per_shard_kv_pool_bytes": kvs,
                    "per_shard_total_bytes": totals,
                    "fits_hbm": bool(totals < hbm),
                }
                if draft is not None:
                    # the draft rides every shard in full (replicated)
                    dtp = totals + draft["total_bytes"]
                    tp_rows[str(t)]["with_draft"] = {
                        "per_shard_total_bytes": dtp,
                        "fits_hbm": bool(dtp < hbm),
                    }
            if tp_rows:
                row["widths"][w]["tp"] = tp_rows
        tiers.append(row)
    only_tp = []
    for r in tiers:
        if any(r["widths"][w]["fits_hbm"] for w in WEIGHT_WIDTHS):
            continue
        fits_at = [
            {"width": w, "tp": int(t)}
            for w in WEIGHT_WIDTHS
            for t, c in sorted(r["widths"][w].get("tp", {}).items(),
                               key=lambda kv_: int(kv_[0]))
            if c.get("fits_hbm")
        ]
        if fits_at:
            only_tp.append({"tier": r["tier"], "fits_at": fits_at})
    only_quant = [
        r["tier"] for r in tiers
        if not r["widths"]["fp32"]["fits_hbm"]
        and not r["widths"]["bf16"]["fits_hbm"]
        and (r["widths"]["int8"]["fits_hbm"]
             or r["widths"]["int4"]["fits_hbm"])
    ]
    return {
        "metric": "serve_largest_fit_tier",
        "value": {w: largest_fit[w] for w in WEIGHT_WIDTHS},
        "unit": f"largest tier under {hbm_gb:g} GB HBM per weight "
                f"width (int8 KV)",
        "scenario": {"max_seqs": max_seqs, "context": context,
                     "page_size": page_size, "weight_block": block,
                     "kv_dtype": "int8",
                     "tp_degrees": [int(t) for t in (tp or ())]},
        "hbm_limit_bytes": int(hbm),
        "tiers": tiers,
        "fits_only_quantized": only_quant,
        "fits_only_tensor_parallel": only_tp,
        **({} if draft is None else {
            "draft": draft,
            "draft_co_resident_largest_fit": {
                w: largest_fit_draft[w] for w in WEIGHT_WIDTHS},
        }),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--hidden", type=int, default=None)
    ap.add_argument("--heads", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--bucket-mb", type=float, default=4.0)
    ap.add_argument("--hbm-gb", type=float, default=DEFAULT_HBM_GB,
                    help="per-device HBM for the fits/exceeds verdict")
    ap.add_argument("--compare", action="store_true",
                    help="replicated-DDP vs ZeRO-3 side by side "
                         "(writes MEMORY_AUDIT.json)")
    ap.add_argument("--train-steps", type=int, default=0,
                    help="ALSO run N real ZeRO-3 steps at the shape "
                         "(slow on CPU hosts; proves the config "
                         "trains, not just compiles)")
    ap.add_argument("--serve", action="store_true",
                    help="serving audit: decode-path bytes per tier "
                         "at fp32/bf16/int8/int4 weight widths "
                         "(writes MEMORY_AUDIT_SERVE.json)")
    ap.add_argument("--max-seqs", type=int, default=4,
                    help="--serve: concurrent serving slots")
    ap.add_argument("--context", type=int, default=1024,
                    help="--serve: per-slot context budget (tokens)")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--weight-block", type=int, default=128,
                    help="--serve: quantization block size")
    ap.add_argument("--tp", type=int, action="append", default=None,
                    help="--serve: tensor-parallel degree for "
                         "per-shard verdict rows (repeatable; "
                         "default: 2 and 4)")
    ap.add_argument("--draft-tier", default="1B",
                    choices=[n for n, _ in SERVE_TIERS] + ["none"],
                    help="--serve: co-resident draft-model tier for "
                         "the speculation verdict (int4 pool + its "
                         "own int8 KV slice added to every width "
                         "row; 'none' disables)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    _force_virtual_devices(args.devices)

    if args.serve:
        doc = run_serve_audit(
            hbm_gb=args.hbm_gb, max_seqs=args.max_seqs,
            context=args.context, page_size=args.page_size,
            block=args.weight_block,
            tp=tuple(args.tp) if args.tp else SERVE_TP_DEGREES,
            draft_tier=(None if args.draft_tier == "none"
                        else args.draft_tier))
        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        out_path = args.out or os.path.join(
            root, "MEMORY_AUDIT_SERVE.json")
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        gb = 1e9
        print(json.dumps({
            "metric": doc["metric"], "value": doc["value"],
            "fits_only_quantized": doc["fits_only_quantized"],
            "fits_only_tensor_parallel":
                doc["fits_only_tensor_parallel"],
            **({} if "draft" not in doc else {
                "draft_tier": doc["draft"]["tier"],
                "draft_gb": round(doc["draft"]["total_bytes"] / gb,
                                  3),
                "draft_co_resident_largest_fit":
                    doc["draft_co_resident_largest_fit"],
            }),
            "tiers_gb": {
                r["tier"]: {
                    w: round(r["widths"][w]["total_bytes"] / gb, 2)
                    for w in WEIGHT_WIDTHS}
                for r in doc["tiers"]},
        }))
        print(f"wrote {out_path}")
        return

    dims = dict(vocab=args.vocab, layers=args.layers,
                hidden=args.hidden, heads=args.heads, seq=args.seq,
                batch=args.batch)
    doc = run_memory_audit(bucket_mb=args.bucket_mb,
                           hbm_gb=args.hbm_gb, **dims)
    if args.train_steps:
        doc["training"] = train_zero3(steps=args.train_steps,
                                      bucket_mb=args.bucket_mb,
                                      **dims)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_path = args.out or os.path.join(root, "MEMORY_AUDIT.json")
    if args.compare or args.out:
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
    gb = 1e9
    print(json.dumps({
        "metric": doc["metric"], "value": doc["value"],
        "n_params": doc["n_params"],
        "ddp_peak_gb": round(
            (doc["replicated_ddp"]["peak_bytes"] or 0) / gb, 2),
        "zero3_peak_gb": round(
            (doc["zero3"]["peak_bytes"] or 0) / gb, 2),
        "ddp_argument_gb": round(
            (doc["replicated_ddp"]["argument_bytes"] or 0) / gb, 2),
        "zero3_argument_gb": round(
            (doc["zero3"]["argument_bytes"] or 0) / gb, 2),
        "replicated_exceeds_hbm": doc["replicated_exceeds_hbm"],
        "zero3_fits_hbm": doc["zero3_fits_hbm"],
    }))
    if args.compare or args.out:
        print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
