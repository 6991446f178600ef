"""One layer's sparse latent attention of a DeepSeek-V3.2 decode step
alone, in its two forms, on the host's clock around whole calls.

The rows are what ``DeepSeekV32Model.decode_step`` runs for one layer
after the selection (``sparse_index.topk_indices``, not timed: both
forms start from it):

- ``gather``: the chosen rows gathered through the page table, then
  ``mla_absorbed`` over them (the decode step's form before the walk);
- ``walk``: the selection turned into a mask from the K-th score
  (``sparse_index.mask_at``), then ``mla_paged`` walking every live row
  of the slot under it (the decode step's form);
- ``walk_given_mask``: the walk alone, the mask made beforehand;
- ``walk_unmasked``: the walk with no selection (every live row seen:
  what the mask costs inside the kernel, by difference).

Every slot is full (its length the extent) and selects ``--topk`` rows
by random index scores.  ``max_abs_diff`` is against ``gather`` (the
unmasked walk is another attention and differs by design).  The walk
reads every live row and the gather only the chosen ones, so the walk
loses once contexts are many times ``--topk``: the extent where the two
cross is what a decode step serving such contexts would choose its form
by.  TPU only:

    python tools/latent_select_ablation.py --out chiprun_out/latent_select.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops.attention_latent import mla_absorbed, mla_paged
from apex_tpu.ops.sparse_index import mask_at, topk_indices

#: DeepSeek-V3.2's attention widths and the cell's slots and pages
WIDTHS = dict(heads=128, dn=128, dr=64, dc=512, dv=128, row=640, page=64,
              slots=32)


def variants(page, K, scale):
    def gather(qn, qr, pool, table, lengths, uk, uv, scores, valid, idx,
               chosen, kth, mask):
        rows = pool[0, jnp.take_along_axis(table, idx // page, axis=1),
                    idx % page]
        return mla_absorbed(qn, qr, rows, chosen, uk, uv, scale)

    def walk(qn, qr, pool, table, lengths, uk, uv, scores, valid, idx,
             chosen, kth, mask):
        mask = mask_at(jnp.where(valid, scores, -jnp.inf), kth, K, valid)
        return walk_given_mask(qn, qr, pool, table, lengths, uk, uv, scores,
                               valid, idx, chosen, kth, mask)

    def walk_given_mask(qn, qr, pool, table, lengths, uk, uv, scores, valid,
                        idx, chosen, kth, mask):
        return mla_paged(qn, qr, pool, 0, table, lengths, uk, uv, scale,
                         selected=mask)

    def walk_unmasked(qn, qr, pool, table, lengths, uk, uv, *rest):
        return mla_paged(qn, qr, pool, 0, table, lengths, uk, uv, scale)

    return {"gather": gather, "walk": walk,
            "walk_given_mask": walk_given_mask,
            "walk_unmasked": walk_unmasked}


def time_ms(fn, args, calls, rounds):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    per_call = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        per_call.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(per_call), min(per_call), max(per_call)


def inputs(key, w, extent, K):
    B, H, page = w["slots"], w["heads"], w["page"]
    width = extent // page
    ks = jax.random.split(key, 7)
    bf = jnp.bfloat16
    normal = lambda k, shape, s=1.0: (
        s * jax.random.normal(k, shape, jnp.float32)).astype(bf)
    pool = normal(ks[0], (1, 1 + B * width, page, w["row"]))
    pool = pool.at[..., w["dc"] + w["dr"]:].set(0)
    perm = np.random.default_rng(0).permutation(B * width) + 1
    table = jnp.asarray(perm.reshape(B, width), jnp.int32)
    lengths = jnp.full((B,), extent, jnp.int32)
    scores = jax.random.normal(ks[1], (B, extent), jnp.float32)
    valid = jnp.arange(extent)[None] < lengths[:, None]
    idx, chosen, kth = jax.jit(topk_indices, static_argnums=1)(
        scores, K, valid)
    mask = jax.jit(lambda s, v, t: mask_at(jnp.where(v, s, -jnp.inf), t, K,
                                           v))(scores, valid, kth)
    return (normal(ks[2], (B, H, w["dn"])), normal(ks[3], (B, H, w["dr"])),
            pool, table, lengths,
            normal(ks[4], (w["dc"], H, w["dn"]), w["dc"] ** -0.5),
            normal(ks[5], (w["dc"], H, w["dv"]), w["dc"] ** -0.5),
            scores, valid, idx, chosen, kth, mask)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--extents", default="4096,7168,16384,24576,32768,65536",
                    help="rows a slot, comma-separated (multiples of 64)")
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--only", default="", help="comma-separated rows")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse (tiny widths, the XLA forms)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.allow_cpu:
        raise SystemExit(f"needs a TPU, found {device.platform}")
    w, K = dict(WIDTHS), args.topk
    extents = [int(v) for v in args.extents.split(",") if v]
    if device.platform != "tpu":
        w.update(heads=8, dn=16, dr=8, dc=32, dv=16, row=128, page=8,
                 slots=3)
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "widths": w, "topk": K, "rows": []}
    rows = variants(w["page"], K, 0.1)
    if args.only:
        rows = {r: rows[r] for r in args.only.split(",")}
    for extent in extents:
        call = inputs(jax.random.PRNGKey(args.seed), w, extent, K)
        want = None
        for row, fn in rows.items():
            fn = jax.jit(fn)
            ms, lo, hi = time_ms(fn, call, args.calls, args.rounds)
            y = np.asarray(fn(*call), np.float32)
            if want is None:
                want = y
            result["rows"].append(dict(
                extent=extent, ratio=extent / K, row=row, ms=ms, ms_min=lo,
                ms_max=hi, max_abs_diff=float(np.abs(y - want).max()),
                max_abs=float(np.abs(want).max())))
            print(json.dumps(result["rows"][-1]), flush=True)
        del call
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
