"""On-TPU kernel validation: pallas-vs-xla parity AND timing.

The TPU analog of the reference's fast-vs-default cross-check
(/root/reference/apex/contrib/multihead_attn/self_multihead_attn.py:26-124)
and its bitwise L1 tier (/root/reference/tests/L1/common/run_test.sh:118-137):
every Pallas kernel is validated against the XLA path on the real chip —
numerically (max abs err vs an fp32 reference) and for speed (median wall
time), with a block-size sweep for flash attention.

Writes KERNELS_TPU.json at the repo root.  Run:

    python tools/kernel_validation.py            # full sweep
    python tools/kernel_validation.py --smoke    # one shape per kernel

Every pallas call here goes through implementation='pallas', and a
selected kernel runs or raises KernelLoweringError (ops/common.py
run_kernel contract) — a Mosaic lowering regression can never be timed
as XLA.  A validator that raises is recorded by name with the compiler's
message and the sweep goes on to the next kernel; the run then exits
non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def _require_tpu():
    plat = jax.devices()[0].platform
    if plat != "tpu":
        raise SystemExit(f"kernel validation must run on TPU (got {plat})")


def _time(fn, *args, iters=100, warmup=1):
    """Amortized ms/call with a device-side repeat loop.

    A host-side call loop would measure dispatch for the small kernels,
    not the kernel, so the loop runs on device via fori_loop, with the
    scalar carry folded into the first operand at 1e-30 scale to build
    a data dependence the compiler cannot hoist.  Residual bias: one
    dispatch / ``iters`` — identical for both implementations being
    compared.  ``fn`` must return a scalar.
    """

    @jax.jit
    def looped(*a):
        def body(_, acc):
            first = (a[0].astype(jnp.float32) + acc * 1e-30).astype(
                a[0].dtype
            )
            return fn(first, *a[1:]).astype(jnp.float32)

        return jax.lax.fori_loop(0, iters, body, jnp.float32(0.0))

    for _ in range(warmup):
        jax.block_until_ready(looped(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(looped(*args))
    return (time.perf_counter() - t0) / iters * 1e3


def _max_err(a, b):
    return float(
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def validate_flash(smoke=False):
    from apex_tpu.ops.attention import (
        FLASH_FP32_MAX_BLOCK_AREA,
        FLASH_FP32_XLA_MAX_SEQ,
        flash_attention,
        mha_reference,
    )

    results = []
    shapes = [(4, 8, 1024, 128), (2, 8, 4096, 128), (1, 4, 8192, 128)]
    dtypes = [jnp.bfloat16, jnp.float32]
    blocks = [(256, 256), (512, 512), (256, 512), (512, 1024),
              (1024, 1024)]
    if smoke:
        shapes, dtypes, blocks = shapes[:1], dtypes[:1], blocks[:2]

    # the r4 verdict flagged the short-seq non-causal window: sweep both
    # causalities at s=1024 (long shapes stay causal-only to bound the
    # chip-session cost; the long-seq win is causality-independent)
    cases = [(shape, causal) for shape in shapes
             for causal in ((True, False) if shape[2] == 1024 else (True,))]
    if smoke:
        cases = cases[:1]
    for shape, causal in cases:
        b, h, s, d = shape
        for dtype in dtypes:
            kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
            q = jax.random.normal(kq, shape, dtype)
            k = jax.random.normal(kk, shape, dtype)
            v = jax.random.normal(kv, shape, dtype)

            def fwd(impl, bq, bk):
                # returns the full tensor (for parity checks)
                return jax.jit(lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    implementation=impl,
                ))

            def fwd_t(impl, bq, bk):
                # scalar-returning variant for timing (4-byte readback)
                return jax.jit(lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    implementation=impl,
                ).astype(jnp.float32)))

            def loss(impl, bq, bk):
                def f(q, k, v):
                    return jnp.sum(flash_attention(
                        q, k, v, causal=causal, block_q=bq, block_k=bk,
                        implementation=impl,
                    ).astype(jnp.float32) ** 2)
                return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

            def loss_t(impl, bq, bk):
                lfn = loss(impl, bq, bk)

                def timed(q, k, v):
                    val, grads = lfn(q, k, v)
                    return val + sum(
                        jnp.sum(g.astype(jnp.float32) ** 2) for g in grads
                    )
                return jax.jit(timed)

            # fp32 ground truth for parity — at HIGHEST matmul precision,
            # or the "reference" itself carries the MXU default's
            # bf16-pass noise and penalizes the more-accurate path
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda a, bb, c: mha_reference(
                    a, bb, c, causal=causal
                ))(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32),
                )

            sweep = {}
            best = None
            for bq, bk in blocks:
                if bq > s or bk > s:
                    continue
                # the wrapper clamps fp32 blocks above the 512x1024 area
                # (vmem stack limit, ops/attention.py _clamp_blocks) —
                # timing those configs would silently duplicate the
                # clamped program and could report a best_block that
                # never ran
                if dtype == jnp.float32 and bq * bk > FLASH_FP32_MAX_BLOCK_AREA:
                    sweep[f"{bq}x{bk}"] = "clamped (fp32 vmem limit)"
                    continue
                try:
                    f = fwd_t("pallas", bq, bk)
                    ms = _time(f, q, k, v)
                except Exception as e:  # lowering failure = loud entry
                    sweep[f"{bq}x{bk}"] = {"error": str(e)[:200]}
                    continue
                sweep[f"{bq}x{bk}"] = round(ms, 3)
                if best is None or ms < best[0]:
                    best = (ms, bq, bk)
            assert best is not None, f"no block config lowered for {shape}"
            _, bq, bk = best

            out_p = jax.device_get(fwd("pallas", bq, bk)(q, k, v))
            out_x = jax.device_get(fwd("xla", bq, bk)(q, k, v))
            xla_ms = _time(fwd_t("xla", bq, bk), q, k, v)

            # backward: pallas vs xla timing + grad parity.  Failure-
            # isolated like the fwd block sweep: a config whose backward
            # fails to compile must become a loud entry, not kill the
            # sweep with every later kernel's rows unwritten (the r5
            # fp32-noncausal vmem OOM cost a whole chip session this way)
            try:
                vp, gp = loss("pallas", bq, bk)(q, k, v)
                vx, gx = loss("xla", bq, bk)(q, k, v)
                gp, gx = jax.device_get((gp, gx))
                bwd_p_ms = _time(loss_t("pallas", bq, bk), q, k, v, iters=30)
                bwd_x_ms = _time(loss_t("xla", bq, bk), q, k, v, iters=30)
                bwd_err = None
            except Exception as e:
                gp = gx = ()
                bwd_p_ms = bwd_x_ms = float("nan")
                bwd_err = str(e)[:300]
            # attention FLOPs: 4*b*h*s^2*d mults (qk + pv), halved by
            # the mask when causal
            flops = (2.0 if causal else 4.0) * b * h * s * s * d
            results.append({
                "kernel": "flash_attention",
                "shape": list(shape),
                "dtype": jnp.dtype(dtype).name,
                "causal": causal,
                "best_block": [bq, bk],
                # fp32 short-seq auto-routes to XLA (dispatch window in
                # ops/attention.py, shared constant so this record
                # matches the actual routing)
                "auto_impl": (
                    "xla"
                    if dtype == jnp.float32 and s <= FLASH_FP32_XLA_MAX_SEQ
                    else "pallas"
                ),
                "block_sweep_ms": sweep,
                "fwd": {
                    "pallas_ms": round(best[0], 3),
                    "xla_ms": round(xla_ms, 3),
                    "speedup": round(xla_ms / best[0], 2),
                    "pallas_tflops": round(flops / best[0] / 1e9, 1),
                    "max_err_vs_fp32": _max_err(out_p, ref),
                    "xla_err_vs_fp32": _max_err(out_x, ref),
                },
                "fwd_bwd": {
                    "error": bwd_err,
                } if bwd_err is not None else {
                    "pallas_ms": round(bwd_p_ms, 3),
                    "xla_ms": round(bwd_x_ms, 3),
                    "speedup": round(bwd_x_ms / bwd_p_ms, 2),
                    "grad_max_rel_err": max(
                        _max_err(a, bb) / (float(jnp.max(jnp.abs(
                            bb.astype(jnp.float32)))) + 1e-6)
                        for a, bb in zip(gp, gx)
                    ),
                },
            })
            print(json.dumps(results[-1]))
    return results


# ---------------------------------------------------------------------------
# fmha-short (single-pass short-sequence attention)
# ---------------------------------------------------------------------------


def validate_fmha_short(smoke=False):
    """Short-vs-flash-vs-XLA sweep at the reference fmha seqlen window
    (+1024): the measured crossover for the FMHA_SHORT_MAX_SEQ
    auto-dispatch boundary is RECORDED here rather than hand-picked —
    an entry whose auto routing loses to either alternative fails the
    gate, telling the next session to move the constant."""
    from apex_tpu.ops.attention import (
        FLASH_FP32_XLA_MAX_SEQ,
        flash_attention,
        mha_reference,
    )
    from apex_tpu.ops.attention_short import (
        default_block_bh,
        fmha_short,
        short_seq_threshold,
    )

    results = []
    b, h, d = 4, 8, 128
    # the reference's per-seqlen kernel window {128,256,384,512} plus
    # 1024 (the flagship pain shape) so the crossover is bracketed
    seqs = [128, 256, 384, 512, 1024]
    dtypes = [jnp.bfloat16, jnp.float32]
    if smoke:
        seqs, dtypes = seqs[:1], dtypes[:1]
    cases = [(s, causal) for s in seqs
             for causal in ((True, False) if s in (512, 1024) else (True,))]
    if smoke:
        cases = cases[:1]
    for s, causal in cases:
        for dtype in dtypes:
            kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
            shape = (b, h, s, d)
            q = jax.random.normal(kq, shape, dtype)
            k = jax.random.normal(kk, shape, dtype)
            v = jax.random.normal(kv, shape, dtype)

            def short_fwd(bb):
                return jax.jit(lambda q, k, v: fmha_short(
                    q, k, v, causal=causal, block_bh=bb,
                    implementation="pallas",
                ))

            def short_fwd_t(bb):
                return jax.jit(lambda q, k, v: jnp.sum(fmha_short(
                    q, k, v, causal=causal, block_bh=bb,
                    implementation="pallas",
                ).astype(jnp.float32)))

            def other_fwd_t(impl):
                return jax.jit(lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, causal=causal, implementation=impl,
                ).astype(jnp.float32)))

            def loss_t(fn_kwargs):
                def f(q, k, v):
                    return jnp.sum(flash_attention(
                        q, k, v, causal=causal, **fn_kwargs
                    ).astype(jnp.float32) ** 2)
                lfn = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

                def timed(q, k, v):
                    val, grads = lfn(q, k, v)
                    return val + sum(
                        jnp.sum(g.astype(jnp.float32) ** 2) for g in grads
                    )
                return jax.jit(timed), lfn

            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda a, bb, c: mha_reference(
                    a, bb, c, causal=causal
                ))(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32),
                )

            # block_bh sweep (the short kernel's analog of the flash
            # block sweep); the auto size is always included
            auto_bb = default_block_bh(s, s, b * h)
            bb_candidates = sorted({1, 2, 4, 8, 16, auto_bb})
            sweep = {}
            best = None
            for bb in bb_candidates:
                if bb > b * h:
                    continue
                try:
                    ms = _time(short_fwd_t(bb), q, k, v)
                except Exception as e:  # lowering failure = loud entry
                    sweep[f"bh{bb}"] = {"error": str(e)[:200]}
                    continue
                sweep[f"bh{bb}"] = round(ms, 3)
                if best is None or ms < best[0]:
                    best = (ms, bb)
            if best is None:
                # nothing lowered: keep a loud row instead of dying with
                # every later kernel's rows unwritten (r5 lesson)
                results.append({
                    "kernel": "fmha_short",
                    "shape": list(shape),
                    "dtype": jnp.dtype(dtype).name,
                    "causal": causal,
                    "block_bh_sweep_ms": sweep,
                    "error": "no block_bh config lowered",
                })
                print(json.dumps(results[-1]))
                continue
            short_ms, bb = best

            out_s = jax.device_get(short_fwd(bb)(q, k, v))
            out_x = jax.device_get(jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, implementation="xla"))(q, k, v))
            flash_ms = _time(other_fwd_t("pallas"), q, k, v)
            xla_ms = _time(other_fwd_t("xla"), q, k, v)

            # backward: short vs flash vs xla + grad parity vs xla
            try:
                short_l, short_lfn = loss_t(dict(
                    implementation="short"))
                xla_l, xla_lfn = loss_t(dict(implementation="xla"))
                flash_l, _ = loss_t(dict(implementation="pallas"))
                _, gp = short_lfn(q, k, v)
                _, gx = xla_lfn(q, k, v)
                gp, gx = jax.device_get((gp, gx))
                bwd_s_ms = _time(short_l, q, k, v, iters=30)
                bwd_f_ms = _time(flash_l, q, k, v, iters=30)
                bwd_x_ms = _time(xla_l, q, k, v, iters=30)
                bwd_err = None
            except Exception as e:
                gp = gx = ()
                bwd_s_ms = bwd_f_ms = bwd_x_ms = float("nan")
                bwd_err = str(e)[:300]

            # what the shipped auto dispatch actually does for this
            # shape (shared constants so the record cannot drift)
            if dtype == jnp.float32 and s <= FLASH_FP32_XLA_MAX_SEQ:
                auto_impl = "xla"
            elif s <= short_seq_threshold():
                auto_impl = "short"
            else:
                auto_impl = "pallas"
            flops = (2.0 if causal else 4.0) * b * h * s * s * d
            results.append({
                "kernel": "fmha_short",
                "shape": list(shape),
                "dtype": jnp.dtype(dtype).name,
                "causal": causal,
                "best_block_bh": bb,
                "auto_impl": auto_impl,
                "block_bh_sweep_ms": sweep,
                "fwd": {
                    "short_ms": round(short_ms, 3),
                    "flash_ms": round(flash_ms, 3),
                    "xla_ms": round(xla_ms, 3),
                    "speedup": round(xla_ms / short_ms, 2),
                    "speedup_vs_flash": round(flash_ms / short_ms, 2),
                    "short_tflops": round(flops / short_ms / 1e9, 1),
                    "max_err_vs_fp32": _max_err(out_s, ref),
                    "xla_err_vs_fp32": _max_err(out_x, ref),
                },
                "fwd_bwd": {
                    "error": bwd_err,
                } if bwd_err is not None else {
                    "short_ms": round(bwd_s_ms, 3),
                    "flash_ms": round(bwd_f_ms, 3),
                    "xla_ms": round(bwd_x_ms, 3),
                    "speedup": round(bwd_x_ms / bwd_s_ms, 2),
                    "speedup_vs_flash": round(bwd_f_ms / bwd_s_ms, 2),
                    "grad_max_rel_err": max(
                        _max_err(a, bb_) / (float(jnp.max(jnp.abs(
                            bb_.astype(jnp.float32)))) + 1e-6)
                        for a, bb_ in zip(gp, gx)
                    ),
                },
            })
            print(json.dumps(results[-1]))
    return results


# ---------------------------------------------------------------------------
# fmha-mid (pipelined mid-sequence attention)
# ---------------------------------------------------------------------------


def validate_fmha_mid(smoke=False):
    """Mid-vs-flash-vs-XLA sweep across the 512 < s <= 2048 band: the
    measured crossover for the FMHA_MID_MAX_SEQ auto-dispatch boundary
    is RECORDED here rather than hand-picked, exactly like the short
    kernel's.  Three gates ride these rows (main()):

    - crossover: a shape auto-routed to the mid kernel must not lose
      to flash or XLA, and a mid-swept shape routed to flash must not
      have left a mid win on the table;
    - flagship: at (s=1024, causal, bf16) the auto-selected
      implementation must be >= 2x the flash kernel's fwd rate (the
      PROFILE_r05 10.2 TF/s hole this kernel exists to close);
    - block-skip: causal must be <= 0.7x full wall time at s=1024 for
      the mid kernel (today the flash kernel measures them EQUAL,
      0.843 vs 0.857 ms — no blocks to skip)."""
    from apex_tpu.ops.attention import (
        FLASH_FP32_XLA_MAX_SEQ,
        flash_attention,
        mha_reference,
    )
    from apex_tpu.ops.attention_mid import (
        default_mid_block_bh,
        default_mid_blocks,
        fmha_mid,
        mid_seq_threshold,
    )
    from apex_tpu.ops.attention_short import short_seq_threshold

    results = []
    d = 128
    # ragged band entries (576/640), the flagship (1024, at the exact
    # flagship bh=64), the band edge (1536/2048), and ONE beyond-window
    # shape (3072) so the raise-the-boundary gate below is reachable —
    # a crossover gate that can never fire is a hand-picked constant
    # with extra steps
    seqs = [576, 640, 1024, 1536, 2048, 3072]
    dtypes = [jnp.bfloat16, jnp.float32]
    if smoke:
        seqs, dtypes = [1024], dtypes[:1]
    cases = [(s, causal) for s in seqs
             for causal in ((True, False) if s in (1024, 2048) else (True,))]
    if smoke:
        cases = cases[:1]
    for s, causal in cases:
        b, h = (8, 8) if s == 1024 else (4, 8) if s < 1024 else (2, 8)
        for dtype in dtypes:
            kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
            shape = (b, h, s, d)
            q = jax.random.normal(kq, shape, dtype)
            k = jax.random.normal(kk, shape, dtype)
            v = jax.random.normal(kv, shape, dtype)

            def mid_fwd(bq, bk, bb):
                return jax.jit(lambda q, k, v: fmha_mid(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    block_bh=bb, implementation="pallas",
                ))

            def mid_fwd_t(bq, bk, bb):
                return jax.jit(lambda q, k, v: jnp.sum(fmha_mid(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    block_bh=bb, implementation="pallas",
                ).astype(jnp.float32)))

            def other_fwd_t(impl):
                return jax.jit(lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, causal=causal, implementation=impl,
                ).astype(jnp.float32)))

            def loss_t(fn_kwargs):
                def f(q, k, v):
                    return jnp.sum(flash_attention(
                        q, k, v, causal=causal, **fn_kwargs
                    ).astype(jnp.float32) ** 2)
                lfn = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

                def timed(q, k, v):
                    val, grads = lfn(q, k, v)
                    return val + sum(
                        jnp.sum(g.astype(jnp.float32) ** 2) for g in grads
                    )
                return jax.jit(timed), lfn

            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda a, bb, c: mha_reference(
                    a, bb, c, causal=causal
                ))(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32),
                )

            # (block_q, block_k, block_bh) sweep: the shipped default
            # plus the plausible neighbours (the mid analog of the
            # flash block sweep / short block_bh sweep)
            # (None, None, None) is the call dispatch makes: the kernel
            # sizes the forward's and the backward's blocks itself; an
            # explicit triple is used by both passes as given
            s_l = s + (-s) % 128
            dbq, dbk = default_mid_blocks(s_l, s_l)
            dbb = default_mid_block_bh(dbq, dbk, b * h)
            default = (None, None, None)
            cands = [default, (dbq, dbk, dbb), (dbq, dbk, 1)]
            for bq, bk in [(128, 128), (256, 256), (256, 512),
                           (512, 256), (512, 512)]:
                if bq > s_l or bk > s_l:
                    continue
                cands.append((bq, bk, default_mid_block_bh(bq, bk, b * h)))
            sweep = {}
            best = None
            default_ms = None
            for bq, bk, bb in dict.fromkeys(cands):
                key = ("default" if (bq, bk, bb) == default
                       else f"{bq}x{bk}xbh{bb}")
                try:
                    ms = _time(mid_fwd_t(bq, bk, bb), q, k, v)
                except Exception as e:  # lowering failure = loud entry
                    sweep[key] = {"error": str(e)[:200]}
                    continue
                sweep[key] = round(ms, 3)
                if (bq, bk, bb) == default:
                    default_ms = ms
                if best is None or ms < best[0]:
                    best = (ms, bq, bk, bb)
            if best is None:
                results.append({
                    "kernel": "fmha_mid",
                    "shape": list(shape),
                    "dtype": jnp.dtype(dtype).name,
                    "causal": causal,
                    "block_sweep_ms": sweep,
                    "error": "no block config lowered",
                })
                print(json.dumps(results[-1]))
                continue
            mid_ms, bq, bk, bb = best

            # parity at the config dispatch actually ships (fall back
            # to the sweep winner only if the default failed to lower)
            pq, pk, pb = default if default_ms is not None \
                else (bq, bk, bb)
            out_m = jax.device_get(mid_fwd(pq, pk, pb)(q, k, v))
            out_x = jax.device_get(jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, implementation="xla"))(q, k, v))
            # the flash comparator runs at ITS shipped defaults — this
            # ratio is exactly "what does dispatch moving to mid buy"
            flash_ms = _time(other_fwd_t("pallas"), q, k, v)
            xla_ms = _time(other_fwd_t("xla"), q, k, v)

            # backward: mid vs flash vs xla + grad parity vs xla
            try:
                mid_l, mid_lfn = loss_t(dict(implementation="mid"))
                xla_l, xla_lfn = loss_t(dict(implementation="xla"))
                flash_l, _ = loss_t(dict(implementation="pallas"))
                _, gp = mid_lfn(q, k, v)
                _, gx = xla_lfn(q, k, v)
                gp, gx = jax.device_get((gp, gx))
                bwd_m_ms = _time(mid_l, q, k, v, iters=30)
                bwd_f_ms = _time(flash_l, q, k, v, iters=30)
                bwd_x_ms = _time(xla_l, q, k, v, iters=30)
                bwd_err = None
            except Exception as e:
                gp = gx = ()
                bwd_m_ms = bwd_f_ms = bwd_x_ms = float("nan")
                bwd_err = str(e)[:300]

            # what the shipped auto dispatch actually does for this
            # shape (shared constants so the record cannot drift)
            if dtype == jnp.float32 and s <= FLASH_FP32_XLA_MAX_SEQ:
                auto_impl = "xla"
            elif s <= short_seq_threshold():
                auto_impl = "short"
            elif s <= mid_seq_threshold():
                auto_impl = "mid"
            else:
                auto_impl = "pallas"
            flops = (2.0 if causal else 4.0) * b * h * s * s * d
            results.append({
                "kernel": "fmha_mid",
                "shape": list(shape),
                "dtype": jnp.dtype(dtype).name,
                "causal": causal,
                "best_block": [bq, bk, bb],
                "auto_impl": auto_impl,
                "block_sweep_ms": sweep,
                "fwd": {
                    "mid_ms": round(mid_ms, 3),
                    # the SHIPPED default config's timing — what auto
                    # dispatch actually runs, and what the crossover /
                    # flagship / block-skip gates judge (the best-of-
                    # sweep number above is the tuning record; gating
                    # on it would vouch for a config dispatch never
                    # uses).  None if the default failed to lower.
                    "default_ms": (
                        None if default_ms is None else round(default_ms, 3)
                    ),
                    "flash_ms": round(flash_ms, 3),
                    "xla_ms": round(xla_ms, 3),
                    "speedup": round(
                        xla_ms / (default_ms or mid_ms), 2),
                    "speedup_vs_flash": round(
                        flash_ms / (default_ms or mid_ms), 2),
                    "best_speedup_vs_flash": round(flash_ms / mid_ms, 2),
                    "mid_tflops": round(
                        flops / (default_ms or mid_ms) / 1e9, 1),
                    "flash_tflops": round(flops / flash_ms / 1e9, 1),
                    "max_err_vs_fp32": _max_err(out_m, ref),
                    "xla_err_vs_fp32": _max_err(out_x, ref),
                },
                "fwd_bwd": {
                    "error": bwd_err,
                } if bwd_err is not None else {
                    "mid_ms": round(bwd_m_ms, 3),
                    "flash_ms": round(bwd_f_ms, 3),
                    "xla_ms": round(bwd_x_ms, 3),
                    "speedup": round(bwd_x_ms / bwd_m_ms, 2),
                    "speedup_vs_flash": round(bwd_f_ms / bwd_m_ms, 2),
                    "grad_max_rel_err": max(
                        _max_err(a, bb_) / (float(jnp.max(jnp.abs(
                            bb_.astype(jnp.float32)))) + 1e-6)
                        for a, bb_ in zip(gp, gx)
                    ),
                },
            })
            print(json.dumps(results[-1]))
    return results


# ---------------------------------------------------------------------------
# fused layer norm
# ---------------------------------------------------------------------------


def validate_layer_norm(smoke=False):
    from apex_tpu.ops.layer_norm import fused_layer_norm_affine

    results = []
    shapes = [(16384, 1024), (8192, 4096), (4096, 8192)]
    dtypes = [jnp.bfloat16, jnp.float32]
    if smoke:
        shapes, dtypes = shapes[:1], dtypes[:1]
    for rows, hidden in shapes:
        for dtype in dtypes:
            x = jax.random.normal(jax.random.PRNGKey(1), (rows, hidden), dtype)
            w = jnp.ones((hidden,), jnp.float32)
            bias = jnp.zeros((hidden,), jnp.float32)

            def f(impl):
                return jax.jit(lambda x: fused_layer_norm_affine(
                    x, w, bias, (hidden,), implementation=impl
                ))

            def f_t(impl):
                return jax.jit(lambda x: jnp.sum(fused_layer_norm_affine(
                    x, w, bias, (hidden,), implementation=impl
                ).astype(jnp.float32)))

            ref = jax.device_get(f("xla")(x.astype(jnp.float32)))
            out_p = jax.device_get(f("pallas")(x))
            # the fair numeric bound is the XLA path on the SAME input
            # dtype: a bf16 output cannot beat its own quantization
            # (one ulp ≈ 8e-3 at unit scale), and both paths pay it
            out_x = jax.device_get(f("xla")(x))
            p_ms = _time(f_t("pallas"), x)
            x_ms = _time(f_t("xla"), x)
            gb = 2 * rows * hidden * jnp.dtype(dtype).itemsize / 1e9
            results.append({
                "kernel": "fused_layer_norm",
                "shape": [rows, hidden],
                "dtype": jnp.dtype(dtype).name,
                "pallas_ms": round(p_ms, 3),
                "xla_ms": round(x_ms, 3),
                "speedup": round(x_ms / p_ms, 2),
                "pallas_gbps": round(gb / (p_ms / 1e3), 1),
                "max_err_vs_fp32": _max_err(out_p, ref),
                "xla_err_vs_fp32": _max_err(out_x, ref),
                # layernorm auto-routes to XLA by these measurements
                # (ops/layer_norm.py); kernel kept for the cross-check tier
                "auto_impl": "xla",
            })
            print(json.dumps(results[-1]))
    return results


# ---------------------------------------------------------------------------
# scaled (masked) softmax
# ---------------------------------------------------------------------------


def validate_softmax(smoke=False):
    from apex_tpu.ops.softmax import (
        scaled_softmax,
        scaled_upper_triang_masked_softmax,
    )

    results = []
    cases = [
        ("scaled_softmax", scaled_softmax, (32, 1024, 1024)),
        ("scaled_upper_triang_masked_softmax",
         scaled_upper_triang_masked_softmax, (32, 1024, 1024)),
        ("scaled_softmax", scaled_softmax, (8, 2048, 2048)),
        ("scaled_upper_triang_masked_softmax",
         scaled_upper_triang_masked_softmax, (8, 2048, 2048)),
    ]
    dtypes = [jnp.bfloat16, jnp.float32]
    if smoke:
        cases, dtypes = cases[:1], dtypes[:1]
    for name, fn, shape in cases:
        for dtype in dtypes:
            x = jax.random.normal(jax.random.PRNGKey(2), shape, dtype)

            def f(impl):
                return jax.jit(lambda x: fn(x, 1.3, implementation=impl))

            def f_t(impl):
                return jax.jit(lambda x: jnp.sum(
                    fn(x, 1.3, implementation=impl).astype(jnp.float32)
                ))

            ref = jax.device_get(f("xla")(x.astype(jnp.float32)))
            out_p = jax.device_get(f("pallas")(x))
            p_ms = _time(f_t("pallas"), x)
            x_ms = _time(f_t("xla"), x)
            results.append({
                "kernel": name,
                "shape": list(shape),
                "dtype": jnp.dtype(dtype).name,
                "pallas_ms": round(p_ms, 3),
                "xla_ms": round(x_ms, 3),
                "speedup": round(x_ms / p_ms, 2),
                "max_err_vs_fp32": _max_err(out_p, ref),
                # standalone softmax auto-routes to XLA by measurement
                # (ops/softmax.py); the kernel is kept for the cross-check
                # tier and superseded by flash attention in real models
                "auto_impl": "xla",
            })
            print(json.dumps(results[-1]))
    return results


# ---------------------------------------------------------------------------
# fused dense / MLP epilogue fusion
# ---------------------------------------------------------------------------


def validate_fused_dense(smoke=False):
    """A/B the "epilogue fusion is XLA's job" claim
    (apex_tpu/fused_dense/__init__.py): the jitted matmul+bias(+GELU)
    chain vs the same ops with ``optimization_barrier`` between them
    (each stage then materializes to HBM — the unfused reference the
    cublasLt epilogue kernels exist to avoid).  Measured like
    attention/LN/softmax instead of asserted by construction."""
    from apex_tpu.fused_dense import (
        fused_dense_function,
        fused_dense_gelu_dense_function,
    )
    from apex_tpu.mlp import MLP

    barrier = jax.lax.optimization_barrier

    def unfused_dense(x, w, b):
        y = barrier(jnp.matmul(x, w.astype(x.dtype)))
        return barrier(y + b.astype(y.dtype))

    def unfused_gelu_dense(x, w1, b1, w2, b2):
        h = unfused_dense(x, w1, b1)
        h = barrier(jax.nn.gelu(h, approximate=True))
        return unfused_dense(h, w2, b2)

    results = []
    rows, hidden, ffn = (2048, 512, 2048) if smoke else (8192, 1024, 4096)
    dtypes = [jnp.bfloat16] if smoke else [jnp.bfloat16, jnp.float32]
    k = jax.random.PRNGKey(3)
    mlp = MLP([hidden, ffn, hidden], activation="relu")
    mlp_params = mlp.init(jax.random.PRNGKey(4))

    def unfused_mlp(params, x):
        last = len(params) - 1
        for i, layer in enumerate(params):
            x = barrier(jnp.matmul(x, layer["weight"].astype(x.dtype)))
            x = barrier(x + layer["bias"].astype(x.dtype))
            if i != last:  # MLP activates between layers only
                x = barrier(jax.nn.relu(x))
        return x

    for dtype in dtypes:
        x = jax.random.normal(k, (rows, hidden), dtype)
        w1 = jax.random.normal(k, (hidden, ffn), jnp.float32) * 0.02
        b1 = jnp.zeros((ffn,), jnp.float32)
        w2 = jax.random.normal(k, (ffn, hidden), jnp.float32) * 0.02
        b2 = jnp.zeros((hidden,), jnp.float32)
        mp = jax.tree.map(lambda p: p.astype(dtype), mlp_params)

        cases = [
            ("fused_dense",
             lambda x: fused_dense_function(x, w1, b1),
             lambda x: unfused_dense(x, w1, b1)),
            ("fused_dense_gelu_dense",
             lambda x: fused_dense_gelu_dense_function(x, w1, b1, w2, b2),
             lambda x: unfused_gelu_dense(x, w1, b1, w2, b2)),
            ("mlp",
             lambda x: mlp.apply(mp, x),
             lambda x: unfused_mlp(mp, x)),
        ]
        for name, fused, unfused in cases:
            f_sum = jax.jit(lambda x, f=fused: jnp.sum(
                f(x).astype(jnp.float32)))
            u_sum = jax.jit(lambda x, f=unfused: jnp.sum(
                f(x).astype(jnp.float32)))
            ref = jax.device_get(
                jax.jit(unfused)(x.astype(jnp.float32))
            )
            out_f = jax.device_get(jax.jit(fused)(x))
            out_u = jax.device_get(jax.jit(unfused)(x))
            f_ms = _time(f_sum, x)
            u_ms = _time(u_sum, x)
            results.append({
                "kernel": name,
                "shape": [rows, hidden, ffn],
                "dtype": jnp.dtype(dtype).name,
                # pallas_/xla_ naming keeps the summary gates uniform:
                # "pallas" = the shipped fused path, "xla" = the
                # barrier-separated unfused reference
                "pallas_ms": round(f_ms, 3),
                "xla_ms": round(u_ms, 3),
                "speedup": round(u_ms / f_ms, 2),
                "max_err_vs_fp32": _max_err(out_f, ref),
                "xla_err_vs_fp32": _max_err(out_u, ref),
                # epilogue fusion is the compiler's job either way; the
                # row RECORDS whether it happened (speedup >= ~1) and
                # gate (1) rejects numeric drift — no pallas dispatch
                # to re-route, hence auto_impl "xla"
                "auto_impl": "xla",
            })
            print(json.dumps(results[-1]))
    return results


def validate_opt_tail(smoke=False):
    """A/B the fused optimizer tail (PROFILE_r05.json's 11.85 ms →
    6.35 ms bandwidth gap): ``FusedAdam(fused_tail=True).step_scaled``
    — ONE multi-tensor pass folding unscale → finiteness → clip →
    Adam → master→bf16 cast over packed buffers — against the
    ``optimization_barrier``-unfused reference chain, where every
    stage of the seed path (the scaler's unscale pass, the finiteness
    reduction, each leaf's moment/update/cast loop) materializes to
    HBM before the next reads it.  Values are identical (barriers
    change no bits), so the row is pure bandwidth: ``achieved_gbs`` is
    the fused pass's effective GB/s over the paper traffic model
    (:func:`apex_tpu.optimizers.fused_tail.tail_traffic_bytes`) — the
    number to read against the 440-vs-819 GB/s capture."""
    from apex_tpu.amp.scaler import all_finite, scale_gradients
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.base import tree_where
    from apex_tpu.optimizers.fused_tail import tail_traffic_bytes

    barrier = jax.lax.optimization_barrier
    layers, hidden = (2, 512) if smoke else (8, 1024)
    ks = jax.random.split(jax.random.PRNGKey(7), layers + 2)
    params = {"emb": 0.02 * jax.random.normal(
        ks[0], (8192, hidden), jnp.bfloat16)}
    for l in range(layers):
        params[f"l{l}"] = {
            "qkv": 0.02 * jax.random.normal(
                ks[l + 1], (hidden, 3 * hidden), jnp.bfloat16),
            "mlp": 0.02 * jax.random.normal(
                ks[l + 1], (hidden, 4 * hidden), jnp.bfloat16),
            "ln": jnp.ones((hidden,), jnp.bfloat16),
        }
    grads = jax.tree.map(
        lambda p: 0.01 * jax.random.normal(
            ks[-1], jnp.shape(p), jnp.float32).astype(p.dtype),
        params)
    inv = jnp.float32(1.0 / 1024.0)

    results = []
    for max_norm in (None, 1.0):
        fused_opt = FusedAdam(lr=1e-3, master_weights=True,
                              fused_tail=True, max_grad_norm=max_norm)
        ref_opt = FusedAdam(lr=1e-3, master_weights=True,
                            max_grad_norm=max_norm)
        f_state = fused_opt.init(params)
        r_state = ref_opt.init(params)

        def out_scalar(p, s):
            return sum(jnp.sum(l.astype(jnp.float32))
                       for l in jax.tree.leaves(p)) + \
                sum(jnp.sum(l.astype(jnp.float32))
                    for l in jax.tree.leaves(s["exp_avg"]))

        def fused_t(x, opt=fused_opt, state=f_state):
            # x rides the scale so the whole update depends on the
            # timing carry (nothing hoistable)
            p, s, _ = opt.step_scaled(state, grads, params,
                                      inv * (1.0 + x * 1e-30))
            return out_scalar(p, s)

        def unfused_t(x, opt=ref_opt, state=r_state):
            # the seed chain with every stage materialized: unscale
            # pass, finiteness pass, then the per-leaf update with its
            # own barrier (each leaf's loop reads/writes HBM alone)
            g = barrier(scale_gradients(grads, inv * (1.0 + x * 1e-30)))
            finite = barrier(all_finite(g))
            new_p, new_s = opt.step(state, g, params)
            new_p = barrier(new_p)
            new_p = tree_where(finite, new_p, params)
            new_s = tree_where(finite, new_s, state)
            return out_scalar(new_p, new_s)

        # parity first: barriers change no values, the fused pass is
        # bit-identical by the tail contract
        pf, sf, _ = jax.jit(
            lambda: fused_opt.step_scaled(f_state, grads, params, inv)
        )()
        pr, sr = jax.jit(
            lambda: ref_opt.step(
                r_state, scale_gradients(grads, inv), params,
                grads_finite=all_finite(grads))
        )()
        err = max(
            _max_err(a, b) for a, b in zip(
                jax.tree.leaves(pf), jax.tree.leaves(pr))
        )
        x0 = jnp.float32(0.0)
        f_ms = _time(fused_t, x0, iters=20)
        u_ms = _time(unfused_t, x0, iters=20)
        nbytes = tail_traffic_bytes(params, fused_opt)
        results.append({
            "kernel": "opt_tail",
            "shape": [layers, hidden,
                      sum(int(jnp.size(l))
                          for l in jax.tree.leaves(params))],
            "dtype": "bfloat16",
            "clip": max_norm is not None,
            # "pallas" = the shipped fused path, "xla" = the barrier-
            # separated unfused chain (the fused_dense convention), so
            # summary gate (2) enforces fused >= unfused
            "pallas_ms": round(f_ms, 3),
            "xla_ms": round(u_ms, 3),
            "speedup": round(u_ms / f_ms, 2),
            "max_err_vs_fp32": err,
            "xla_err_vs_fp32": 0.0,
            "traffic_bytes": nbytes,
            "achieved_gbs": round(nbytes / (f_ms * 1e-3) / 1e9, 1),
            "unfused_gbs": round(nbytes / (u_ms * 1e-3) / 1e9, 1),
            "auto_impl": "pallas",
            "note": "queued against PROFILE_r05's 11.85 ms / 440 GB/s "
                    "optimizer-tail capture (paper bw 819 GB/s)",
        })
        print(json.dumps(results[-1]))
    return results


def validate_fmha_decode(smoke=False):
    """Decode-tier sweep (the fourth attention rung): the Pallas paged
    decode kernel vs the XLA paged reference across serving shapes —
    batch {1,8,64,256} x cache length {512,2048,8192} x KV dtype
    {bf16, fp32, int8}, plus chunked-prefill cells at s_q in {64, 256}
    (the scheduler's prompt-ingestion chunk attending over cache + its
    own just-written pages, held to the same never-lose-to-XLA bar as
    s_q=1), plus head-sharded cells at tp in {2, 4} (a tensor-parallel
    shard's local h/tp slice of the pool at the SAME shuffled page
    table + ragged lengths every shard shares, with the shard concat
    checked against the full-h call) — plus the end-to-end gate:
    GREEDY generation through the
    full serving stack (paged cache + fmha_decode + continuous
    batching, monolithic AND chunked prefill) must produce
    token-identical output to the naive full-recompute reference at
    kv_dtype=None.

    Two gates ride these rows in main(): parity (gate 1, relative to
    the XLA path's own error vs the fp32 ground truth — both paths pay
    the same output-dtype quantization) and no-loss (gate 2: the
    kernel must not lose to the XLA reference at ANY swept cell —
    decode is explicit-dispatch, so a losing cell is a kernel bug, not
    a crossover to move).  ``decode_gbs`` is the number that matters at
    decode's ~2 FLOPs/byte: achieved KV-stream bandwidth."""
    from apex_tpu.ops.attention_decode import (
        fmha_decode,
        paged_attention_reference,
    )
    from apex_tpu.ops.quantization import quantize_rows

    results = []
    h, d, ps = 4, 128, 64
    kv_block = 128
    batches = [1, 8, 64, 256]
    caches = [512, 2048, 8192]
    kvs = ["bfloat16", "float32", "int8"]
    if smoke:
        batches, caches, kvs = [8], [512], ["bfloat16", "int8"]
    for b in batches:
        for cache in caches:
            npp = cache // ps
            pool_pages = 1 + b * npp        # page 0 = reserved null
            key = jax.random.PRNGKey(0)
            k0, k1, k2, k3 = jax.random.split(key, 4)
            km = jax.random.normal(k0, (pool_pages, h, ps, d),
                                   jnp.bfloat16)
            vm = jax.random.normal(k1, (pool_pages, h, ps, d),
                                   jnp.bfloat16)
            q = jax.random.normal(k2, (b, h, 1, d), jnp.bfloat16)
            # REAL paging: a shuffled physical layout, and ragged
            # lengths so odd sequences end on a partially-filled page
            perm = jax.random.permutation(
                k3, jnp.arange(1, pool_pages, dtype=jnp.int32))
            page_table = perm[: b * npp].reshape(b, npp)
            lengths = jnp.where(
                jnp.arange(b) % 2 == 0, cache, cache - ps // 2 - 1
            ).astype(jnp.int32)
            for kv in kvs:
                if kv == "int8":
                    def q8(pages):
                        vals, scales = quantize_rows(
                            pages.reshape(-1, d).astype(jnp.float32),
                            kv_block)
                        return (vals.reshape(pages.shape),
                                scales.reshape(*pages.shape[:-1], -1))

                    kp, ks = q8(km)
                    vp, vs = q8(vm)
                else:
                    dt = jnp.dtype(kv)
                    kp, vp = km.astype(dt), vm.astype(dt)
                    ks = vs = None
                kwargs = dict(k_scales=ks, v_scales=vs,
                              kv_block=kv_block)

                def fwd_t(impl):
                    return jax.jit(
                        lambda q, kp, vp: jnp.sum(fmha_decode(
                            q, kp, vp, page_table, lengths,
                            implementation=impl, **kwargs,
                        ).astype(jnp.float32)))

                # fp32 ground truth on a subset of sequences, over a
                # sub-pool of ONLY the pages that subset references
                # (converting the whole b=256 x 8k pool to fp32 would
                # transiently eat ~8 GB — parity does not need every
                # page, timing does).  Sub-pool index 0 keeps the null-
                # page convention; the remapped table is dense 1..n.
                bp = min(b, 32)
                used = jnp.concatenate([
                    jnp.zeros((1,), jnp.int32),
                    page_table[:bp].reshape(-1),
                ])
                sub_table = (1 + jnp.arange(
                    bp * npp, dtype=jnp.int32)).reshape(bp, npp)
                with jax.default_matmul_precision("highest"):
                    kp_s = jnp.take(kp, used, axis=0)
                    vp_s = jnp.take(vp, used, axis=0)
                    if kv == "int8":
                        from apex_tpu.ops.attention_decode import (
                            _dequant_pages,
                        )
                        kr = _dequant_pages(
                            kp_s, jnp.take(ks, used, axis=0), kv_block)
                        vr = _dequant_pages(
                            vp_s, jnp.take(vs, used, axis=0), kv_block)
                    else:
                        kr, vr = (kp_s.astype(jnp.float32),
                                  vp_s.astype(jnp.float32))
                    ref = jax.jit(
                        lambda q, kr, vr: paged_attention_reference(
                            q, kr, vr, sub_table, lengths[:bp]))(
                        q[:bp].astype(jnp.float32), kr, vr)
                out_p = jax.device_get(jax.jit(
                    lambda q, kp, vp: fmha_decode(
                        q, kp, vp, page_table[:bp], lengths[:bp],
                        implementation="pallas", **kwargs,
                    ))(q[:bp], kp, vp))
                out_x = jax.device_get(jax.jit(
                    lambda q, kp, vp: fmha_decode(
                        q, kp, vp, page_table[:bp], lengths[:bp],
                        implementation="xla", **kwargs,
                    ))(q[:bp], kp, vp))
                iters = 10 if smoke else 50
                p_ms = _time(fwd_t("pallas"), q, kp, vp, iters=iters)
                x_ms = _time(fwd_t("xla"), q, kp, vp, iters=iters)
                kv_bytes = 2 * b * npp * ps * h * d * \
                    jnp.dtype(kp.dtype).itemsize
                results.append({
                    "kernel": "fmha_decode",
                    "shape": [b, h, 1, d],
                    "cache_len": cache,
                    "page_size": ps,
                    "dtype": kv,
                    "causal": True,
                    "auto_impl": "pallas",
                    "fwd": {
                        "pallas_ms": round(p_ms, 3),
                        "xla_ms": round(x_ms, 3),
                        "speedup": round(x_ms / p_ms, 2),
                        "decode_gbs": round(
                            kv_bytes / (p_ms * 1e-3) / 1e9, 1),
                        "max_err_vs_fp32": _max_err(out_p, ref),
                        "xla_err_vs_fp32": _max_err(out_x, ref),
                    },
                })
                print(json.dumps(results[-1]))

    # ---- chunked-prefill cells: s_q in {64, 256} — the serving
    # scheduler's prompt-ingestion chunk attends over the prior cache
    # AND its own just-written pages (write-before-attend), per-row
    # causal at positions lengths - sq + i.  Same rows, same gates:
    # parity is gate (1) and the never-lose-to-XLA bar is gate (2) —
    # the chunk path is explicit dispatch exactly like s_q = 1, so a
    # losing cell is a kernel bug (likely the VMEM-bounded block_h
    # pick), not a crossover to move.
    sqs = [64] if smoke else [64, 256]
    sq_kvs = ["bfloat16"] if smoke else ["bfloat16", "int8"]
    for sq in sqs:
        b, cache = 8, (512 if smoke else 2048)
        npp = cache // ps
        pool_pages = 1 + b * npp
        key = jax.random.PRNGKey(sq)
        k0, k1, k2, k3 = jax.random.split(key, 4)
        km = jax.random.normal(k0, (pool_pages, h, ps, d), jnp.bfloat16)
        vm = jax.random.normal(k1, (pool_pages, h, ps, d), jnp.bfloat16)
        q = jax.random.normal(k2, (b, h, sq, d), jnp.bfloat16)
        perm = jax.random.permutation(
            k3, jnp.arange(1, pool_pages, dtype=jnp.int32))
        page_table = perm[: b * npp].reshape(b, npp)
        # ragged: odd sequences' chunks end mid-page (lengths count the
        # chunk's own just-written tokens, all >= sq)
        lengths = jnp.where(
            jnp.arange(b) % 2 == 0, cache, cache - ps // 2 - 1
        ).astype(jnp.int32)
        for kv in sq_kvs:
            if kv == "int8":
                def q8s(pages):
                    vals, scales = quantize_rows(
                        pages.reshape(-1, d).astype(jnp.float32),
                        kv_block)
                    return (vals.reshape(pages.shape),
                            scales.reshape(*pages.shape[:-1], -1))

                kp, ks = q8s(km)
                vp, vs = q8s(vm)
            else:
                kp, vp = km, vm
                ks = vs = None
            kwargs = dict(k_scales=ks, v_scales=vs, kv_block=kv_block)

            def fwd_t(impl):
                return jax.jit(
                    lambda q, kp, vp: jnp.sum(fmha_decode(
                        q, kp, vp, page_table, lengths,
                        implementation=impl, **kwargs,
                    ).astype(jnp.float32)))

            with jax.default_matmul_precision("highest"):
                if kv == "int8":
                    from apex_tpu.ops.attention_decode import (
                        _dequant_pages,
                    )
                    kr = _dequant_pages(kp, ks, kv_block)
                    vr = _dequant_pages(vp, vs, kv_block)
                else:
                    kr, vr = (kp.astype(jnp.float32),
                              vp.astype(jnp.float32))
                ref = jax.jit(
                    lambda q, kr, vr: paged_attention_reference(
                        q, kr, vr, page_table, lengths))(
                    q.astype(jnp.float32), kr, vr)
            out_p = jax.device_get(jax.jit(
                lambda q, kp, vp: fmha_decode(
                    q, kp, vp, page_table, lengths,
                    implementation="pallas", **kwargs))(q, kp, vp))
            out_x = jax.device_get(jax.jit(
                lambda q, kp, vp: fmha_decode(
                    q, kp, vp, page_table, lengths,
                    implementation="xla", **kwargs))(q, kp, vp))
            iters = 10 if smoke else 50
            p_ms = _time(fwd_t("pallas"), q, kp, vp, iters=iters)
            x_ms = _time(fwd_t("xla"), q, kp, vp, iters=iters)
            kv_bytes = 2 * b * npp * ps * h * d * \
                jnp.dtype(kp.dtype).itemsize
            results.append({
                "kernel": "fmha_decode",
                "shape": [b, h, sq, d],
                "cache_len": cache,
                "page_size": ps,
                "dtype": kv,
                "causal": True,
                "auto_impl": "pallas",
                "chunked_prefill": True,
                "fwd": {
                    "pallas_ms": round(p_ms, 3),
                    "xla_ms": round(x_ms, 3),
                    "speedup": round(x_ms / p_ms, 2),
                    "decode_gbs": round(
                        kv_bytes / (p_ms * 1e-3) / 1e9, 1),
                    "max_err_vs_fp32": _max_err(out_p, ref),
                    "xla_err_vs_fp32": _max_err(out_x, ref),
                },
            })
            print(json.dumps(results[-1]))

    # ---- speculative-verify cells: s_q in {4, 8, 16} — the
    # draft-and-verify step scores k drafts + 1 bonus row per slot in
    # one pass, per-row causal at lengths - sq + i exactly like the
    # chunk cells above but at the SMALL s_q the k-selection trade
    # lives at (acceptance saturates long before chunk sizes).  Ragged
    # lengths and shuffled page tables as everywhere; same parity gate
    # (1) and never-lose-to-XLA gate (2) — the TPU capture must cover
    # the verify shape family before anyone trusts a speculative
    # speedup measured through it.
    vsqs = [8] if smoke else [4, 8, 16]
    vkvs = ["bfloat16"] if smoke else ["bfloat16", "int8"]
    for sq in vsqs:
        b, cache = 8, (512 if smoke else 2048)
        npp = cache // ps
        pool_pages = 1 + b * npp
        key = jax.random.PRNGKey(1000 + sq)
        k0, k1, k2, k3 = jax.random.split(key, 4)
        km = jax.random.normal(k0, (pool_pages, h, ps, d), jnp.bfloat16)
        vm = jax.random.normal(k1, (pool_pages, h, ps, d), jnp.bfloat16)
        q = jax.random.normal(k2, (b, h, sq, d), jnp.bfloat16)
        perm = jax.random.permutation(
            k3, jnp.arange(1, pool_pages, dtype=jnp.int32))
        page_table = perm[: b * npp].reshape(b, npp)
        # ragged: slots mid-generation sit at arbitrary offsets inside
        # their last page (lengths count the verify rows themselves,
        # current token + k drafts, all >= sq)
        lengths = jnp.where(
            jnp.arange(b) % 2 == 0, cache, cache - ps // 2 - 1
        ).astype(jnp.int32)
        for kv in vkvs:
            if kv == "int8":
                def q8v(pages):
                    vals, scales = quantize_rows(
                        pages.reshape(-1, d).astype(jnp.float32),
                        kv_block)
                    return (vals.reshape(pages.shape),
                            scales.reshape(*pages.shape[:-1], -1))

                kp, ks = q8v(km)
                vp, vs = q8v(vm)
            else:
                kp, vp = km, vm
                ks = vs = None
            kwargs = dict(k_scales=ks, v_scales=vs, kv_block=kv_block)

            def fwd_t(impl):
                return jax.jit(
                    lambda q, kp, vp: jnp.sum(fmha_decode(
                        q, kp, vp, page_table, lengths,
                        implementation=impl, **kwargs,
                    ).astype(jnp.float32)))

            with jax.default_matmul_precision("highest"):
                if kv == "int8":
                    from apex_tpu.ops.attention_decode import (
                        _dequant_pages,
                    )
                    kr = _dequant_pages(kp, ks, kv_block)
                    vr = _dequant_pages(vp, vs, kv_block)
                else:
                    kr, vr = (kp.astype(jnp.float32),
                              vp.astype(jnp.float32))
                ref = jax.jit(
                    lambda q, kr, vr: paged_attention_reference(
                        q, kr, vr, page_table, lengths))(
                    q.astype(jnp.float32), kr, vr)
            out_p = jax.device_get(jax.jit(
                lambda q, kp, vp: fmha_decode(
                    q, kp, vp, page_table, lengths,
                    implementation="pallas", **kwargs))(q, kp, vp))
            out_x = jax.device_get(jax.jit(
                lambda q, kp, vp: fmha_decode(
                    q, kp, vp, page_table, lengths,
                    implementation="xla", **kwargs))(q, kp, vp))
            iters = 10 if smoke else 50
            p_ms = _time(fwd_t("pallas"), q, kp, vp, iters=iters)
            x_ms = _time(fwd_t("xla"), q, kp, vp, iters=iters)
            kv_bytes = 2 * b * npp * ps * h * d * \
                jnp.dtype(kp.dtype).itemsize
            results.append({
                "kernel": "fmha_decode",
                "shape": [b, h, sq, d],
                "cache_len": cache,
                "page_size": ps,
                "dtype": kv,
                "causal": True,
                "auto_impl": "pallas",
                "speculative_verify": True,
                "fwd": {
                    "pallas_ms": round(p_ms, 3),
                    "xla_ms": round(x_ms, 3),
                    "speedup": round(x_ms / p_ms, 2),
                    "decode_gbs": round(
                        kv_bytes / (p_ms * 1e-3) / 1e9, 1),
                    "max_err_vs_fp32": _max_err(out_p, ref),
                    "xla_err_vs_fp32": _max_err(out_x, ref),
                },
            })
            print(json.dumps(results[-1]))

    # ---- tree-verify cells: ancestor-masked s_q in {4, 8, 16} — the
    # TREE speculation shape (docs/attention.md fourth rung).  The
    # verify rows stop being one chain: a static (sq, sq) ancestor
    # matrix over the candidate tree replaces the in-window causal
    # triangle, so each row attends the committed cache plus exactly
    # its root-to-node path.  Heap-shaped trees (parents[r] =
    # (r-1)//2) give real branching at every depth; the dense XLA
    # reference runs under the SAME mask.  Ragged lengths and shuffled
    # page tables as everywhere; same parity gate (1) and
    # never-lose-to-XLA gate (2).
    tsqs = [8] if smoke else [4, 8, 16]
    for sq in tsqs:
        ancestor_tree = tuple(-1 if r == 0 else (r - 1) // 2
                              for r in range(sq))
        b, cache = 8, (512 if smoke else 2048)
        npp = cache // ps
        pool_pages = 1 + b * npp
        key = jax.random.PRNGKey(3000 + sq)
        k0, k1, k2, k3 = jax.random.split(key, 4)
        km = jax.random.normal(k0, (pool_pages, h, ps, d), jnp.bfloat16)
        vm = jax.random.normal(k1, (pool_pages, h, ps, d), jnp.bfloat16)
        q = jax.random.normal(k2, (b, h, sq, d), jnp.bfloat16)
        perm = jax.random.permutation(
            k3, jnp.arange(1, pool_pages, dtype=jnp.int32))
        page_table = perm[: b * npp].reshape(b, npp)
        lengths = jnp.where(
            jnp.arange(b) % 2 == 0, cache, cache - ps // 2 - 1
        ).astype(jnp.int32)
        from apex_tpu.serving.speculate import tree_ancestors

        amask = tree_ancestors(ancestor_tree)
        kwargs = dict(kv_block=kv_block, ancestor=amask)

        def fwd_t(impl):
            return jax.jit(
                lambda q, kp, vp: jnp.sum(fmha_decode(
                    q, kp, vp, page_table, lengths,
                    implementation=impl, **kwargs,
                ).astype(jnp.float32)))

        with jax.default_matmul_precision("highest"):
            ref = jax.jit(
                lambda q, kr, vr: paged_attention_reference(
                    q, kr, vr, page_table, lengths, ancestor=amask))(
                q.astype(jnp.float32), km.astype(jnp.float32),
                vm.astype(jnp.float32))
        out_p = jax.device_get(jax.jit(
            lambda q, kp, vp: fmha_decode(
                q, kp, vp, page_table, lengths,
                implementation="pallas", **kwargs))(q, km, vm))
        out_x = jax.device_get(jax.jit(
            lambda q, kp, vp: fmha_decode(
                q, kp, vp, page_table, lengths,
                implementation="xla", **kwargs))(q, km, vm))
        iters = 10 if smoke else 50
        p_ms = _time(fwd_t("pallas"), q, km, vm, iters=iters)
        x_ms = _time(fwd_t("xla"), q, km, vm, iters=iters)
        kv_bytes = 2 * b * npp * ps * h * d * \
            jnp.dtype(km.dtype).itemsize
        results.append({
            "kernel": "fmha_decode",
            "shape": [b, h, sq, d],
            "cache_len": cache,
            "page_size": ps,
            "dtype": "bfloat16",
            "causal": True,
            "auto_impl": "pallas",
            "tree_verify": True,
            "fwd": {
                "pallas_ms": round(p_ms, 3),
                "xla_ms": round(x_ms, 3),
                "speedup": round(x_ms / p_ms, 2),
                "decode_gbs": round(
                    kv_bytes / (p_ms * 1e-3) / 1e9, 1),
                "max_err_vs_fp32": _max_err(out_p, ref),
                "xla_err_vs_fp32": _max_err(out_x, ref),
            },
        })
        print(json.dumps(results[-1]))

    # ---- head-sharded cells: the tensor-parallel decode layout.  A
    # tp shard calls fmha_decode on its OWN head slice of the pool
    # ((pages, h/tp, ps, d) — heads are independent in attention, so
    # no kernel change) while every shard drives the SAME shuffled
    # page table and ragged lengths: that is the shared-free-list
    # invariant the serving tp contract rests on.  Each cell runs all
    # tp shards, checks the head-concat of the shard outputs against
    # the full-h single-call output (must be the identical math) AND
    # against the fp32 reference, and times one shard — the per-shard
    # KV stream is 1/tp of the bytes, which is the whole point.  Same
    # parity gate (1) and never-lose-to-XLA gate (2) as every other
    # decode row.
    import numpy as np

    hs_h = 8
    hs_tps = [2] if smoke else [2, 4]
    hs_kvs = ["bfloat16"] if smoke else ["bfloat16", "int8"]
    b, cache = 8, (512 if smoke else 2048)
    npp = cache // ps
    pool_pages = 1 + b * npp
    key = jax.random.PRNGKey(2000)
    k0, k1, k2, k3 = jax.random.split(key, 4)
    km = jax.random.normal(k0, (pool_pages, hs_h, ps, d), jnp.bfloat16)
    vm = jax.random.normal(k1, (pool_pages, hs_h, ps, d), jnp.bfloat16)
    q = jax.random.normal(k2, (b, hs_h, 1, d), jnp.bfloat16)
    perm = jax.random.permutation(
        k3, jnp.arange(1, pool_pages, dtype=jnp.int32))
    page_table = perm[: b * npp].reshape(b, npp)
    lengths = jnp.where(
        jnp.arange(b) % 2 == 0, cache, cache - ps // 2 - 1
    ).astype(jnp.int32)
    for kv in hs_kvs:
        if kv == "int8":
            def q8h(pages):
                vals, scales = quantize_rows(
                    pages.reshape(-1, d).astype(jnp.float32),
                    kv_block)
                return (vals.reshape(pages.shape),
                        scales.reshape(*pages.shape[:-1], -1))

            kp, ks = q8h(km)
            vp, vs = q8h(vm)
        else:
            kp, vp = km, vm
            ks = vs = None

        def hs_kwargs(lo, hi):
            # a shard's pool slice: heads [lo:hi) of every page (and
            # of the per-block scales, which ride the head axis too)
            return dict(
                k_scales=None if ks is None else ks[:, lo:hi],
                v_scales=None if vs is None else vs[:, lo:hi],
                kv_block=kv_block)

        # fp32 ground truth + the full-h single-call pallas output the
        # shard concat must reproduce
        with jax.default_matmul_precision("highest"):
            if kv == "int8":
                from apex_tpu.ops.attention_decode import (
                    _dequant_pages,
                )
                kr = _dequant_pages(kp, ks, kv_block)
                vr = _dequant_pages(vp, vs, kv_block)
            else:
                kr, vr = (kp.astype(jnp.float32),
                          vp.astype(jnp.float32))
            ref = jax.jit(
                lambda q, kr, vr: paged_attention_reference(
                    q, kr, vr, page_table, lengths))(
                q.astype(jnp.float32), kr, vr)
        out_full = jax.device_get(jax.jit(
            lambda q, kp, vp: fmha_decode(
                q, kp, vp, page_table, lengths,
                implementation="pallas",
                **hs_kwargs(0, hs_h)))(q, kp, vp))
        for tp in hs_tps:
            hl = hs_h // tp
            shards_p, shards_x = [], []
            for r in range(tp):
                lo, hi = r * hl, (r + 1) * hl
                kwr = hs_kwargs(lo, hi)
                shards_p.append(jax.device_get(jax.jit(
                    lambda q, kp, vp: fmha_decode(
                        q, kp, vp, page_table, lengths,
                        implementation="pallas", **kwr))(
                    q[:, lo:hi], kp[:, lo:hi], vp[:, lo:hi])))
                shards_x.append(jax.device_get(jax.jit(
                    lambda q, kp, vp: fmha_decode(
                        q, kp, vp, page_table, lengths,
                        implementation="xla", **kwr))(
                    q[:, lo:hi], kp[:, lo:hi], vp[:, lo:hi])))
            cat_p = np.concatenate(shards_p, axis=1)
            cat_x = np.concatenate(shards_x, axis=1)
            kw0 = hs_kwargs(0, hl)

            def fwd_t(impl):
                return jax.jit(
                    lambda q, kp, vp: jnp.sum(fmha_decode(
                        q, kp, vp, page_table, lengths,
                        implementation=impl, **kw0,
                    ).astype(jnp.float32)))

            iters = 10 if smoke else 50
            p_ms = _time(fwd_t("pallas"), q[:, :hl], kp[:, :hl],
                         vp[:, :hl], iters=iters)
            x_ms = _time(fwd_t("xla"), q[:, :hl], kp[:, :hl],
                         vp[:, :hl], iters=iters)
            kv_bytes = 2 * b * npp * ps * hl * d * \
                jnp.dtype(kp.dtype).itemsize
            results.append({
                "kernel": "fmha_decode",
                "shape": [b, hl, 1, d],
                "cache_len": cache,
                "page_size": ps,
                "dtype": kv,
                "causal": True,
                "auto_impl": "pallas",
                "head_sharded": True,
                "tp": tp,
                "heads_global": hs_h,
                "shard_vs_full_max_diff": _max_err(cat_p, out_full),
                "fwd": {
                    "pallas_ms": round(p_ms, 3),
                    "xla_ms": round(x_ms, 3),
                    "speedup": round(x_ms / p_ms, 2),
                    "decode_gbs": round(
                        kv_bytes / (p_ms * 1e-3) / 1e9, 1),
                    "max_err_vs_fp32": _max_err(cat_p, ref),
                    "xla_err_vs_fp32": _max_err(cat_x, ref),
                },
            })
            print(json.dumps(results[-1]))

    # ---- end-to-end greedy-generation gate: the paged serving stack
    # must reproduce the unpaged full-recompute reference exactly
    import numpy as np

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    model = GPTModel(GPTConfig(
        vocab_size=512, num_layers=2, hidden_size=512,
        num_attention_heads=4, max_position_embeddings=64,
        compute_dtype=jnp.bfloat16, remat=False,
    ))
    params = model.init(jax.random.PRNGKey(0))
    bgen, sp, new = 4, 16, 32
    rng = np.random.RandomState(0)
    prompts = rng.randint(1, 512, (bgen, sp)).astype(np.int32)
    plens = np.array([sp, sp - 3, sp - 7, 5], np.int32)
    for i in range(bgen):
        prompts[i, plens[i]:] = 0
    ref_toks = model.generate_reference(params, prompts, plens, new,
                                        mesh=mesh)
    got = model.generate(params, prompts, plens, new, mesh=mesh,
                         page_size=16, max_seqs=2, harvest_every=4)
    match = all(list(ref_toks[i]) == got[i] for i in range(bgen))
    # the chunked scheduler must land on the same tokens: 3 chunks per
    # full prompt (C=8), prefix caching on so the shared admit path is
    # exercised on hardware too
    got_c = model.generate(params, prompts, plens, new, mesh=mesh,
                           page_size=16, max_seqs=2, harvest_every=4,
                           prefill_chunk=8, prefix_cache=True)
    match_c = all(list(ref_toks[i]) == got_c[i] for i in range(bgen))
    # speculative decoding must ALSO land on the reference tokens: the
    # verify step's k+1-row pass and the rollback-by-length-truncation
    # must be invisible in the output (the n-gram draft source makes
    # acceptance patterns data-dependent, so this exercises variable
    # multi-token advances on hardware)
    got_s = model.generate(params, prompts, plens, new, mesh=mesh,
                           page_size=16, max_seqs=2, harvest_every=4,
                           speculate_k=4)
    match_s = all(list(ref_toks[i]) == got_s[i] for i in range(bgen))
    results.append({
        "kernel": "decode_generation",
        "shape": [bgen, sp, new],
        "dtype": "bfloat16",
        "greedy_match": bool(match),
        "chunked_greedy_match": bool(match_c),
        "speculative_greedy_match": bool(match_s),
        "note": "paged serving stack (continuous batching, 2 slots / "
                "4 requests; monolithic AND chunked+prefix-cache "
                "prefill AND speculative k=4) vs naive full-recompute "
                "greedy reference",
    })
    print(json.dumps(results[-1]))
    return results


def validate_dequant_matmul(smoke=False):
    """Weight-dequantizing matmul cells (the quantized-weight-pool
    serving path): the in-tile dequant Pallas kernel vs the XLA
    dequantize-then-dot reference across decode-shape dots — token
    rows m in {1, 8, 64} x the three projection shapes a decode layer
    streams (qkv h→3h, FFN up h→4h, FFN down 4h→h at h=2048) x weight
    width {int8, packed int4}.

    Ground truth is the fp32 dot against the MATERIALIZED dequantized
    matrix under highest matmul precision — both implementations
    compute that same math, so parity rides main()'s relative gate (1)
    and the never-lose-to-XLA bar is gate (2): the kernel's entire
    reason to exist is streaming FEWER bytes than the wide temp the
    XLA path materializes, so a losing cell is a kernel bug.
    ``weight_gbs`` is the number that matters at decode's
    weight-streaming roofline: achieved quantized-weight bandwidth
    (qweight + scales bytes per call)."""
    from apex_tpu.ops.dequant_matmul import (
        dequant_matmul,
        dequantize_weight,
        quantize_weight,
    )

    results = []
    block = 128
    ms = [1, 8, 64]
    shapes = [("qkv", 2048, 6144), ("ffn_up", 2048, 8192),
              ("ffn_down", 8192, 2048)]
    widths = ["int8", "int4"]
    if smoke:
        ms, shapes = [8], [("qkv", 512, 1536)]
    for name, k, n in shapes:
        key = jax.random.PRNGKey(hash(name) % (1 << 31))
        kw, kx = jax.random.split(key)
        w = jax.random.normal(kw, (k, n), jnp.float32)
        for wd in widths:
            wq = quantize_weight(w, wd, block)
            qv = wq["q8"] if wd == "int8" else wq["q4"]
            scales = wq["scales"]
            # ONE ground truth per (shape, width): the dequantized
            # matrix both implementations encode, at full precision
            with jax.default_matmul_precision("highest"):
                wref = dequantize_weight(wq)
            for m in ms:
                x = jax.random.normal(kx, (m, k), jnp.float32)
                with jax.default_matmul_precision("highest"):
                    ref = jax.device_get(jnp.dot(x, wref))

                def fwd_t(impl):
                    return jax.jit(
                        lambda x, qv, s: jnp.sum(dequant_matmul(
                            x, qv, s, weight_dtype=wd,
                            implementation=impl,
                        ).astype(jnp.float32)))

                run = lambda impl: jax.device_get(jax.jit(
                    lambda x, qv, s: dequant_matmul(
                        x, qv, s, weight_dtype=wd,
                        implementation=impl))(x, qv, scales))
                out_p = run("pallas")
                out_x = run("xla")
                iters = 10 if smoke else 50
                p_ms = _time(fwd_t("pallas"), x, qv, scales,
                             iters=iters)
                x_ms = _time(fwd_t("xla"), x, qv, scales, iters=iters)
                w_bytes = int(qv.nbytes) + int(scales.nbytes)
                results.append({
                    "kernel": "dequant_matmul",
                    "proj": name,
                    "shape": [m, k, n],
                    "dtype": wd,
                    "block_size": block,
                    "auto_impl": "pallas",
                    "fwd": {
                        "pallas_ms": round(p_ms, 3),
                        "xla_ms": round(x_ms, 3),
                        "speedup": round(x_ms / p_ms, 2),
                        "weight_gbs": round(
                            w_bytes / (p_ms * 1e-3) / 1e9, 1),
                        "max_err_vs_fp32": _max_err(out_p, ref),
                        "xla_err_vs_fp32": _max_err(out_x, ref),
                    },
                })
                print(json.dumps(results[-1]))
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "KERNELS_TPU.json",
    ))
    args = ap.parse_args()
    _require_tpu()
    t0 = time.time()
    entries = []
    raised = []
    for validate in (validate_flash, validate_fmha_short,
                     validate_fmha_mid, validate_layer_norm,
                     validate_softmax, validate_fused_dense,
                     validate_opt_tail, validate_fmha_decode,
                     validate_dequant_matmul):
        try:
            entries += validate(smoke=args.smoke)
        except Exception as e:  # noqa: BLE001 — the sweep's boundary:
            # one kernel Mosaic refuses must not hide the verdict on
            # the others; recorded by name, reported, exit non-zero
            traceback.print_exc()
            raised.append({"validator": validate.__name__,
                           "error": f"{type(e).__name__}: {e}"[:4000]})
    from apex_tpu.ops.attention_mid import mid_seq_threshold
    from apex_tpu.ops.attention_short import short_seq_threshold
    doc = {
        "device": str(jax.devices()[0]),
        "jax_version": jax.__version__,
        "smoke": bool(args.smoke),
        "wall_s": round(time.time() - t0, 1),
        # the crossovers the shipped dispatch ladder used during this
        # capture; fmha_short / fmha_mid rows record whether they match
        # the measurement
        "fmha_short_max_seq": short_seq_threshold(),
        "fmha_mid_max_seq": mid_seq_threshold(),
        "entries": entries,
        "raised": raised,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out} ({len(entries)} entries, "
          f"{doc['wall_s']}s)")
    # summary gates:
    # (1) numeric: the pallas path must track the fp32 reference about as
    #     tightly as the XLA path does (TPU default matmul precision puts
    #     a bf16-pass noise floor under BOTH paths, so the bound is
    #     relative), and backward grads must agree with XLA
    bad = []
    for e in entries:
        f = e.get("fwd", e)
        err = f.get("max_err_vs_fp32", 0.0)
        ref_err = max(f.get("xla_err_vs_fp32", 0.0), 1e-3)
        if err > 5 * ref_err:
            bad.append((e, f"fwd err {err} > 5x xla err {ref_err}"))
        grad_err = e.get("fwd_bwd", {}).get("grad_max_rel_err", 0.0)
        if grad_err > 0.1:
            bad.append((e, f"grad rel err {grad_err} > 0.1"))
    # (2) speed: every kernel whose AUTO mode picks pallas must be at
    #     least at parity with XLA (kernels that auto-route to XLA are
    #     recorded measurements, not regressions)
    for e in entries:
        # fmha_short / fmha_mid rows are judged by the crossover gates
        # (3)-(5) below: their auto_impl can name a DIFFERENT kernel
        # than the one the row times, so fwd.speedup is not an
        # auto-path measurement there
        if e.get("kernel") in ("fmha_short", "fmha_mid"):
            continue
        if (e.get("auto_impl", "pallas") == "pallas"
                and e.get("fwd", e).get("speedup", 1.0) < 1.0):
            bad.append((e, "pallas slower than xla on an auto-pallas path"))
    # (3) crossover: a shape the auto dispatch routes to the short
    #     kernel must not lose to EITHER alternative, and a short-swept
    #     shape routed to flash must not have left a short win on the
    #     table — either failure means FMHA_SHORT_MAX_SEQ needs moving
    #     to what this capture measured
    for e in entries:
        if e.get("kernel") != "fmha_short" or "fwd" not in e:
            continue
        f = e["fwd"]
        if e.get("auto_impl") == "short":
            if f.get("speedup", 1.0) < 1.0:
                bad.append((e, "auto-short shape slower than xla"))
            if f.get("speedup_vs_flash", 1.0) < 1.0:
                bad.append((e, "auto-short shape slower than flash"))
        elif e.get("auto_impl") == "pallas" and \
                f.get("speedup_vs_flash", 0.0) > 1.0:
            bad.append((e, "short kernel beats flash beyond the "
                           "FMHA_SHORT_MAX_SEQ boundary — raise it"))
    # (3b) mid crossover, same record-don't-hand-pick contract: a shape
    #     the ladder routes to the mid kernel must not lose to flash or
    #     XLA, and a mid-swept shape routed past the mid window must
    #     not have left a mid win on the table
    for e in entries:
        if e.get("kernel") != "fmha_mid" or "fwd" not in e:
            continue
        f = e["fwd"]
        if e.get("auto_impl") == "mid":
            if f.get("default_ms") is None:
                # the SHIPPED config must lower on an auto-mid shape:
                # without it the ratios below fall back to the sweep
                # winner — a config dispatch never runs — while real
                # training silently degrades to XLA at this shape
                bad.append((e, "shipped default block config failed to "
                               "lower on an auto-mid shape"))
            if f.get("speedup", 1.0) < 1.0:
                bad.append((e, "auto-mid shape slower than xla"))
            if f.get("speedup_vs_flash", 1.0) < 1.0:
                bad.append((e, "auto-mid shape slower than flash — "
                               "move FMHA_MID_MAX_SEQ (or the fp32 "
                               "window) to what this capture measured"))
        elif e.get("auto_impl") == "pallas" and \
                f.get("speedup_vs_flash", 0.0) > 1.0:
            bad.append((e, "mid kernel beats flash beyond the "
                           "FMHA_MID_MAX_SEQ boundary — raise it"))
    # (4) flagship: the whole point of the mid tier is the 10-TF/s hole
    #     at (s=1024, causal, bf16) — the implementation the ladder
    #     selects there must be at least 2x the flash kernel's fwd rate
    # (5) block-skip: causal must be measurably cheaper than full for
    #     the mid kernel at s=1024 (<= 0.7x wall time; the flash kernel
    #     measures them EQUAL there — no blocks to skip)
    flag = {}
    for e in entries:
        if e.get("kernel") == "fmha_mid" and "fwd" in e and \
                e["shape"][2] == 1024 and e["dtype"] == "bfloat16":
            flag[bool(e["causal"])] = e
    if True in flag:
        e = flag[True]
        if e.get("auto_impl") == "mid" and \
                e["fwd"].get("speedup_vs_flash", 0.0) < 2.0:
            bad.append((e, "selected impl under 2x flash fwd at the "
                           "flagship shape (s=1024 causal bf16)"))
    # (6) decode: the serving stack's greedy generation must be token-
    #     identical to the full-recompute reference (the paged cache +
    #     fused decode changed no semantics).  The per-cell no-loss
    #     gate for fmha_decode rows is gate (2) — decode is explicit
    #     dispatch, so a losing cell is a kernel bug, not a crossover.
    for e in entries:
        if e.get("kernel") == "decode_generation" and \
                not e.get("greedy_match", True):
            bad.append((e, "paged greedy generation diverged from the "
                           "full-recompute reference"))
        if e.get("kernel") == "decode_generation" and \
                not e.get("chunked_greedy_match", True):
            bad.append((e, "CHUNKED-prefill greedy generation diverged "
                           "from the full-recompute reference"))
        if e.get("kernel") == "decode_generation" and \
                not e.get("speculative_greedy_match", True):
            bad.append((e, "SPECULATIVE greedy generation diverged "
                           "from the full-recompute reference — the "
                           "verify step / acceptance rule changed "
                           "semantics"))
    if True in flag and False in flag:
        # same shipped config on both sides (best-of-sweep could pick
        # different blocks per causality and fake a skip win)
        c_ms = flag[True]["fwd"].get("default_ms") \
            or flag[True]["fwd"]["mid_ms"]
        f_ms = flag[False]["fwd"].get("default_ms") \
            or flag[False]["fwd"]["mid_ms"]
        ratio = c_ms / f_ms
        if ratio > 0.7:
            bad.append((flag[True],
                        f"causal/full wall ratio {ratio:.2f} > 0.7 at "
                        "s=1024 — the causal block-skip is not firing"))
    for e, why in bad:
        print(f"GATE FAIL: {e['kernel']} {e['shape']} {e['dtype']}: {why}")
    for r in raised:
        print(f"RAISED: {r['validator']}: {r['error'][:600]}")
    if bad or raised:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
