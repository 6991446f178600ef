"""The fmha_mid forward alone, from a device trace: where its time went.

PR 29's step 0 (PERF.md section 6).  ``parent_call`` is the forward
schedule as it was before PR 29 (scores ``(q, k)``, the k loop on the
grid with K/V blocks re-fetched a q block, the running max and sum
lane-broadcast into ``(block_q, 128)`` scratch), kept HERE with one
switch a cost, so that each can be taken out alone and the rest timed.
A stubbed variant computes wrong numbers on purpose; only the ``parent``
row is a correct kernel.  The ``shipped`` row is
``apex_tpu.ops.fmha_mid``'s forward as the tree has it.

Times are device times: each variant's Mosaic call has a name of its
own, and its duration is read off the profiler's "XLA Ops" line (a
host-clock loop measured 1,748 GB/s against an 819 GB/s peak, PERF.md
section 6, PR 24).  TPU only:

    python tools/fmha_fwd_ablation.py --out chiprun_out/fmha_fwd_ablation.json
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import fmha_mid

_NEG = -1e30
_LANES = 128

#: The cells' calls of the mid band: (b, h, sq, sk, d, causal, mask bias).
SHAPES = {
    "train-345m": (16, 16, 1024, 1024, 64, True, False),
    "train-1.3b-dp2tp2": (4, 8, 2048, 2048, 128, True, False),
    "latent-chunk": (1, 32, 2048, 2048, 192, False, True),
    "gpt2-prefill": (1, 16, 960, 960, 64, True, False),
}

#: One switch a cost the issue sized; "all" takes every one out.
ABLATIONS = {
    "parent": {},
    "reductions_stubbed": {"stub_reduce": True},
    "ml_broadcast_stores_stubbed": {"stub_ml": True},
    "acc_rescale_stubbed": {"stub_corr": True},
    "kv_maps_clamped": {"clamp_kv": True},
    "bf16_operands": {"bf16": True},
    "all": {"stub_reduce": True, "stub_ml": True, "clamp_kv": True,
            "bf16": True},
}


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _parent_kernel(*refs, scale, causal, bq, bk, bb, num_k, has_bias,
                   stub_reduce=False, stub_ml=False, stub_corr=False,
                   bf16=False):
    (q_ref, k_ref, v_ref), rest = refs[:3], refs[3:]
    bias_ref = None
    if has_bias:
        bias_ref, rest = rest[0], rest[1:]
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    j, kb = pl.program_id(1), pl.program_id(2)
    last_kb = num_k - 1
    if causal:
        last_kb = jnp.minimum(last_kb, ((j + 1) * bq - 1) // bk)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body(masked):
        if masked:
            q_idx = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_idx = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = k_idx <= q_idx
        for bi in range(bb):
            q = q_ref[bi].astype(jnp.float32) * scale
            if bf16:
                s = _dot(q.astype(q_ref.dtype), k_ref[bi], ((1,), (1,)))
            else:
                s = _dot(q, k_ref[bi].astype(jnp.float32), ((1,), (1,)))
            if has_bias:
                s = s + bias_ref[0].astype(jnp.float32)
            if masked:
                s = jnp.where(mask, s, _NEG)
            m_prev, l_prev = m_ref[bi, :, 0:1], l_ref[bi, :, 0:1]
            # a stubbed reduction reads one lane: no cross-lane work
            m_cur = s[:, 0:1] if stub_reduce else jnp.max(
                s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + (p[:, 0:1] if stub_reduce else jnp.sum(
                p, axis=-1, keepdims=True))
            if bf16:
                pv = _dot(p.astype(v_ref.dtype), v_ref[bi], ((1,), (0,)))
            else:
                pv = _dot(p, v_ref[bi].astype(jnp.float32), ((1,), (0,)))
            acc_ref[bi] = pv + (acc_ref[bi] if stub_corr
                                else acc_ref[bi] * corr)
            if stub_ml:                      # one lane instead of 128
                m_ref[bi, :, 0:1] = m_new
                l_ref[bi, :, 0:1] = l_new
            else:
                m_ref[bi] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[bi] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    run = kb <= last_kb
    if causal:
        need = kb * bk + (bk - 1) > j * bq
        pl.when(jnp.logical_and(run, need))(lambda: _body(True))
        pl.when(jnp.logical_and(run, jnp.logical_not(need)))(
            lambda: _body(False))
    else:
        pl.when(run)(lambda: _body(False))

    @pl.when(kb == last_kb)
    def _finalize():
        for bi in range(bb):
            l = jnp.maximum(l_ref[bi, :, 0:1], 1e-30)
            o_ref[bi] = (acc_ref[bi] / l).astype(o_ref.dtype)
            lse_ref[bi, 0] = m_ref[bi, :, 0] + jnp.log(l[:, 0])


def parent_call(q, k, v, bias, *, name, scale, causal, bq=256, bk=256, bb=8,
                clamp_kv=False, **stubs):
    """The pre-PR-29 forward on flat padded ``(bh, s, d_p)`` operands."""
    bh, sq, d_p = q.shape
    num_q, num_k = sq // bq, k.shape[1] // bk

    def kv_map(i, j, kb):
        if clamp_kv and causal:
            kb = jnp.minimum(kb, ((j + 1) * bq - 1) // bk)
        return (i, kb, 0)

    in_specs = [pl.BlockSpec((bb, bq, d_p), lambda i, j, kb: (i, j, 0)),
                pl.BlockSpec((bb, bk, d_p), kv_map),
                pl.BlockSpec((bb, bk, d_p), kv_map)]
    inputs = [q, k, v]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((1, bq, bk), lambda i, j, kb: (0, j, kb)))
        inputs.append(bias)
    return pl.pallas_call(
        functools.partial(_parent_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, bb=bb, num_k=num_k,
                          has_bias=bias is not None, **stubs),
        grid=(bh // bb, num_q, num_k),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bb, bq, d_p), lambda i, j, kb: (i, j, 0)),
                   pl.BlockSpec((bb, 1, bq), lambda i, j, kb: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d_p), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bb, bq, d_p), jnp.float32),
                        pltpu.VMEM((bb, bq, _LANES), jnp.float32),
                        pltpu.VMEM((bb, bq, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name,
    )(*inputs)


def _inputs(shape, seed):
    b, h, sq, sk, d, causal, masked = SHAPES[shape]
    kq, kk, kv, kb = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda key, s: jax.random.normal(
        key, (b, h, s, d), jnp.float32).astype(jnp.bfloat16)
    bias = None
    if masked:
        keep = jax.random.bernoulli(kb, 0.5, (1, 1, sq, sk))
        bias = jnp.where(keep.at[..., 0].set(True), 0.0, _NEG)
    return mk(kq, sq), mk(kk, sk), mk(kv, sk), bias


def _flat(x, s_to):
    """(b, h, s, d) -> the parent kernel's padded (bh, s_p, d_p)."""
    b, h, s, d = x.shape
    return jnp.pad(x.reshape(b * h, s, d),
                   ((0, 0), (0, s_to - s), (0, (-d) % _LANES)))


def _kernel_ms(trace_dir, names):
    """Durations (ms) of chip 0's operations, by the kernel name each
    carries."""
    from jax.profiler import ProfileData
    out = {name: [] for name in names}
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    for name in names:
                        if name in e.name:
                            out[name].append(e.duration_ns / 1e6)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--seed", type=int, default=29)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("fmha_fwd_ablation times a TPU; none is attached")

    result = {"device_kind": jax.devices()[0].device_kind, "shapes": {}}
    for shape in args.shapes:
        b, h, sq, sk, d, causal, _ = SHAPES[shape]
        q, k, v, bias = _inputs(shape, args.seed)
        pad = lambda s: s + (-s) % 256
        qf, kf, vf = _flat(q, pad(sq)), _flat(k, pad(sk)), _flat(v, pad(sk))
        bias_f = None if bias is None else jnp.pad(
            bias[0], ((0, 0), (0, pad(sq) - sq), (0, pad(sk) - sk)))
        # variant -> (jitted call, its arguments, its kernel's name)
        runs = {}
        for name, stubs in ABLATIONS.items():
            if pad(sk) != sk:
                break             # the parent copy carries no kv-pad mask
            if not causal:        # nothing to clamp
                stubs = {k: v for k, v in stubs.items() if k != "clamp_kv"}
                if not stubs and name != "parent":
                    continue
            f = functools.partial(
                parent_call, name=f"fmha_fwd_ablation.{name}.",
                scale=d ** -0.5, causal=causal, **stubs)
            runs[name] = (jax.jit(f), (qf, kf, vf, bias_f),
                          f"fmha_fwd_ablation.{name}.")
        shipped = jax.jit(lambda q, k, v, bias: fmha_mid(
            q, k, v, causal=causal, bias=bias, bias_requires_grad=False,
            implementation="pallas"))
        runs["shipped"] = (shipped, (q, k, v, bias), "fmha_mid.fwd")
        for f, a, _ in runs.values():
            jax.block_until_ready(f(*a))
        with tempfile.TemporaryDirectory() as tdir:
            jax.profiler.start_trace(tdir)
            for f, a, _ in runs.values():
                for _ in range(args.iters):
                    out = f(*a)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            ms = _kernel_ms(tdir, [r[2] for r in runs.values()])
        rows = {}
        for name, (_, _, kernel) in runs.items():
            durs = ms[kernel]
            rows[name] = {"ms_median": statistics.median(durs) if durs
                          else None, "calls": len(durs)}
            print(f"{shape:20s} {name:30s} {rows[name]['ms_median']}",
                  flush=True)
        result["shapes"][shape] = rows
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
