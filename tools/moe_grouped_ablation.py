"""The held experts of one prefill chunk alone: the tile loop, the
compiler's grouped product and the grouped Mosaic product, on the host's
clock around whole calls.

PR 38's tool (PERF.md section 6, docs/models.md "Grouped computation").
Every row is ``HeldExpertsMLP._experts`` in its layer-stacked form (a
traced layer index into a stack of ``--layers`` layers, as the models'
scans call it) at a cell's chunk: the same sort, layout and final
gather, and one of

- ``loop_<T>``: the XLA loop over tiles of ``T`` rows, each slicing its
  expert out of the stack (what a chunk ran through PR 37 at T = 128);
- ``ragged_dot``: ``lax.ragged_dot`` over the sorted rows with the
  experts' sizes as ``group_sizes``, the layer's experts indexed out of
  the stack (the compiler's own grouped product);
- ``grouped_<T>_<MiB>``: ``apex_tpu.ops.moe_grouped.grouped_swiglu`` at
  tiles of ``T`` rows and weight blocks of at most that many MiB (the
  shipped path is 128 rows and 8 MiB);
- ``layout_only_<T>``: the grouped form with the product taken out (it
  hands its rows back): what the sort, the layout, the two gathers and
  the counters cost; ``product_alone_<T>_<MiB>``: the two kernels on the
  rows that layout gives them; ``product_one_expert_<T>_<MiB>``: the
  same with every tile made the first expert's, so that the weights are
  fetched once a call (the arithmetic without the weight traffic).

The pairs are drawn per token without replacement from a Zipf-like law
over the experts whose exponent is searched so that the largest load
over the mean is ``--skew`` (the seeded routers of the cells read ~10);
``--skew 1`` is a uniform draw.  A row's ``max_abs_diff`` is against
``loop_128``.  TPU only:

    python tools/moe_grouped_ablation.py --out chiprun_out/moe_grouped_ablation.json
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.ops import moe_grouped
from apex_tpu.transformer import moe
from apex_tpu.transformer.moe import HeldExpertsMLP

#: a cell's chunk: hidden, expert width, experts routed over, experts
#: held, choices a token, tokens a chunk
SHAPES = {
    "xing4-chunk": dict(h=3584, f=1024, experts=64, held=64, k=4, n=4096),
    "dsv32-chunk": dict(h=7168, f=2048, experts=256, held=16, k=8, n=2048),
    "trinity-chunk": dict(h=3072, f=3072, experts=256, held=32, k=4,
                          n=1024),
}


def draw(rng, n, k, experts, skew):
    """(n, k) distinct experts a token, and the largest load over the
    mean that came out."""
    def at(s):
        logits = -s * np.log(np.arange(1, experts + 1))
        noisy = logits[None] + rng.gumbel(size=(n, experts))
        chosen = np.argsort(-noisy, axis=1)[:, :k]
        load = np.bincount(chosen.reshape(-1), minlength=experts)
        return chosen, load.max() / load.mean()
    lo, hi = 0.0, 4.0
    for _ in range(20):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if at(mid)[1] < skew else (lo, mid)
    chosen, got = at(hi if skew > 1 else 0.0)
    perm = rng.permutation(experts)       # the hot experts anywhere
    return perm[chosen].astype(np.int32), float(got)


def ragged(layer, experts, x, chosen, g, held, token_valid, index):
    n, h = x.shape
    k, nh = layer.top_k, len(held)
    lookup = np.full((layer.num_experts,), nh, np.int32)
    lookup[list(held)] = np.arange(nh, dtype=np.int32)
    expert = jnp.where(token_valid[:, None],
                       jnp.asarray(lookup)[chosen], nh).reshape(-1)
    sizes = jnp.sum(expert[:, None] == jnp.arange(nh)[None], axis=0,
                    dtype=jnp.int32)
    order = jnp.argsort(expert, stable=True)
    rows = x[order // k]
    w = {name: lax.dynamic_index_in_dim(experts[name], index, 0, False)
         for name in experts}
    dot = functools.partial(lax.ragged_dot, group_sizes=sizes,
                            preferred_element_type=jnp.float32)
    act = (jax.nn.silu(dot(rows, w["w_gate"]))
           * dot(rows, w["w_up"])).astype(x.dtype)
    out = dot(act, w["w_down"]).astype(x.dtype)
    gate = jnp.where(expert < nh, g.reshape(-1), 0.0)
    back = jnp.argsort(order)
    return jnp.sum((out[back].astype(jnp.float32) * gate[:, None]
                    ).reshape(n, k, h), axis=1)


def variants(layer, held, tiles, blocks_mib):
    """Row name -> ``(function, arguments of the call -> its own)``."""
    valid = lambda x: jnp.ones((x.shape[0],), bool)
    same = lambda *call: call

    def through_experts(T, grouped, product=moe_grouped.grouped_swiglu):
        def f(experts, x, chosen, g, index):
            moe.grouped_swiglu = product        # read while TRACED
            try:
                return layer._experts(experts, x, chosen, g, held, valid(x),
                                      T, index, grouped)[0]
            finally:
                moe.grouped_swiglu = moe_grouped.grouped_swiglu
        return f

    def blocks(mib):
        # the product at weight blocks of at most ``mib`` MiB (a module
        # constant, read when the call is made)
        def product(*args):
            shipped = moe_grouped.MOE_GROUPED_BLOCK_BYTES
            moe_grouped.MOE_GROUPED_BLOCK_BYTES = mib * 1024 * 1024
            try:
                return moe_grouped.grouped_swiglu(*args)
            finally:
                moe_grouped.MOE_GROUPED_BLOCK_BYTES = shipped
        return product

    def layout(T, given=None):
        # the product taken out (it hands its rows back): the sort, the
        # layout, both gathers and the counters
        def stub(rows, w_gate, w_up, w_down, tile_expert, live, at):
            if given is not None:
                given.update(rows=rows, tile_expert=tile_expert, live=live)
            return rows
        return through_experts(T, True, stub)

    def product_alone(T, mib, one_expert=False):
        # the two kernels on the rows the layout gives them;
        # ``one_expert``: every tile the first expert's (the weights are
        # fetched once a call: the arithmetic alone)
        def given_by_layout(experts, x, chosen, g, index):
            def f(*call):
                given = {}
                layout(T, given)(*call)
                return given
            given = jax.jit(f)(experts, x, chosen, g, index)
            if one_expert:
                given["tile_expert"] = jnp.zeros_like(given["tile_expert"])
            return (given["rows"], experts, given["tile_expert"],
                    given["live"], index)
        return (lambda rows, experts, tile_expert, live, index: blocks(mib)(
            rows, experts["w_gate"], experts["w_up"], experts["w_down"],
            tile_expert, live, index)), given_by_layout

    out = {f"loop_{T}": (through_experts(T, False), same) for T in (128, 256)}
    out["ragged_dot"] = (lambda experts, x, chosen, g, index: ragged(
        layer, experts, x, chosen, g, held, valid(x), index), same)
    for T in tiles:
        out[f"layout_only_{T}"] = (layout(T), same)
        for mib in blocks_mib:
            out[f"grouped_{T}_{mib}"] = (
                through_experts(T, True, blocks(mib)), same)
            out[f"product_alone_{T}_{mib}"] = product_alone(T, mib)
            out[f"product_one_expert_{T}_{mib}"] = product_alone(
                T, mib, True)
    return out


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


def device_ops(fn, args, calls=3, top=14):
    """The costliest operations of chip 0 over ``calls`` traced calls:
    (ms a call, count a call, the instruction's first 150 characters)."""
    import collections
    import glob
    import tempfile

    from jax.profiler import ProfileData
    total, count = collections.Counter(), collections.Counter()
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True):
            for plane in ProfileData.from_file(path).planes:
                if plane.name != "/device:TPU:0":
                    continue
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for e in line.events:
                            total[e.name[:150]] += e.duration_ns / 1e6
                            count[e.name[:150]] += 1
    return [(ms / calls, count[name] / calls, name)
            for name, ms in total.most_common(top)]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="xing4-chunk,dsv32-chunk")
    ap.add_argument("--tokens", default="",
                    help="tokens a chunk in place of each shape's, "
                    "comma-separated (where the two forms cross)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--skew", default="10,1")
    ap.add_argument("--tiles", default="128,256")
    ap.add_argument("--blocks-mib", default="4,8")
    ap.add_argument("--only", default="", help="comma-separated rows")
    ap.add_argument("--profile", action="store_true",
                    help="each row's costliest device operations too")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse (tiny shapes, interpret mode)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.allow_cpu:
        raise SystemExit(f"needs a TPU, found {device.platform}")
    ints = lambda s: [int(v) for v in s.split(",") if v]
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind}, "rows": []}
    for name, tokens in ((name, tokens)
                         for name in args.shapes.split(",")
                         for tokens in ints(args.tokens) or [None]):
        shape = dict(SHAPES[name], **({"n": tokens} if tokens else {}))
        if args.allow_cpu and device.platform != "tpu":
            shape.update(h=128, f=128, n=shape["n"] // 8)
        h, f, k, n = (shape[key] for key in ("h", "f", "k", "n"))
        nh = shape["held"]
        layer = HeldExpertsMLP(h, f, shape["experts"], top_k=k)
        held = tuple(range(0, shape["experts"], shape["experts"] // nh))
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        w = lambda key, c, width: (jax.random.normal(
            key, (args.layers, nh, c, width), jnp.bfloat16)
            * c ** -0.5).astype(jnp.bfloat16)
        experts = {"w_gate": w(keys[0], h, f), "w_up": w(keys[1], h, f),
                   "w_down": w(keys[2], f, h)}
        x = jax.random.normal(keys[3], (n, h), jnp.bfloat16)
        g = jnp.full((n, k), 1.0 / k, jnp.float32)
        index = jnp.int32(args.layers - 1)
        rows = variants(layer, held, ints(args.tiles), ints(args.blocks_mib))
        if args.only:
            rows = {r: rows[r] for r in args.only.split(",")}
        for skew in (float(s) for s in args.skew.split(",")):
            chosen, got = draw(np.random.default_rng(args.seed), n, k,
                               shape["experts"], skew)
            chosen = jnp.asarray(chosen)
            want = None
            for row, (fn, own) in rows.items():
                fn, call = jax.jit(fn), own(experts, x, chosen, g, index)
                try:
                    ms, lo, hi = time_ms(fn, call, args.calls, args.rounds)
                except Exception as e:       # a form the compiler refuses
                    result["rows"].append(dict(
                        shape=name, tokens=n, skew=got, row=row,
                        error=f"{type(e).__name__}: {str(e)[:300]}"))
                    print(json.dumps(result["rows"][-1]), flush=True)
                    continue
                y = np.asarray(fn(*call), np.float32)
                if want is None:
                    want = y
                whole = y.shape == want.shape and "layout" not in row
                result["rows"].append(dict(
                    shape=name, tokens=n, load_max_over_mean=got, row=row,
                    ms=ms,
                    ms_min=lo, ms_max=hi, max_abs_diff=float(
                        np.abs(y - want).max()) if whole else None,
                    max_abs=float(np.abs(want).max())))
                if args.profile:
                    result["rows"][-1]["device_ops"] = device_ops(fn, call)
                print(json.dumps(result["rows"][-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
