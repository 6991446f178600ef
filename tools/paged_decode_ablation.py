"""The paged decode kernel alone, from a device trace: pages a grid step.

PR 34's tool (PERF.md section 6, docs/attention.md "Pages a grid step").
``parent_call`` is the walk as it was through PR 33 (ONE page a grid
step, every head's body unrolled, the query prepared and the mask built
once a page AND a head, the running max and sum lane-broadcast into
``(rows, 128)`` scratch), kept HERE: it is the ``parent`` row, and
``tests/test_attention_decode.py`` holds the shipped kernel at one page
a step to its bits.  The ``shipped`` row is
``apex_tpu.ops.attention_decode.fmha_decode`` as the tree has it, at the
pages a step its rule computes; ``shipped_<P>_pages`` forces ``P``;
``heads_rolled`` leaves the loop over a program's heads rolled (right
numbers, another schedule).  A ``*_stubbed`` row is the shipped kernel
with one cost taken out while it is traced (it computes wrong numbers on
purpose):

- ``copies_stubbed``: no page copy is started or waited for (the grid,
  the scalar work and the arithmetic over whatever the tiles hold);
- ``dots_stubbed``: both products replaced by a broadcast of a column;
- ``softmax_stubbed``: ``exp`` the identity, row max and sum a column.

Times are device times: each variant's Mosaic call carries a name of its
own and its duration is read off the profiler's "XLA Ops" line.  What a
kernel costs a process BEFORE its first result is on the host's clock:
the three host stages of ONE call (trace, lowering to a module, backend
compile, around ``jax.jit(...).trace / .lower / .compile``), and every
variant's first call after its compile beside its second (the program's
load on the device is in the first) with the compiled code's bytes.
TPU only:

    python tools/paged_decode_ablation.py --out chiprun_out/paged_decode_ablation.json
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import glob
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import attention_decode as ad
from apex_tpu.ops.attention import _NEG_INF, _interpret
from apex_tpu.ops.attention_decode import _DecodeConfig
from apex_tpu.ops.common import shape_struct
from apex_tpu.telemetry.spans import kernel_name

_LANES = 128
HBM_BYTES_PER_S = 819e9          # TPU v5e (benchmarks/rooflines.py)

#: The cells' calls: slots, query heads, K/V heads, d, page size, table
#: width, the walk's bound, window (0: a full layer), fused rotation,
#: and the (lo, hi) context lengths of the halves of the batch.
SHAPES = {
    "gpt2-decode-batch": dict(
        b=32, h=16, h_kv=16, d=64, page=64, width=16, max_pages=None,
        window=0, rope=False, lengths=((33, 640),)),
    "trinity-window": dict(
        b=24, h=48, h_kv=8, d=128, page=64, width=81, max_pages=65,
        window=4096, rope=True, lengths=((384, 1536), (6272, 12800))),
    "trinity-full": dict(
        b=24, h=48, h_kv=8, d=128, page=64, width=200, max_pages=None,
        window=0, rope=False, lengths=((384, 1536), (6272, 12800))),
}


# ------------------------------------------------- the walk through PR 33
def _parent_kernel(*refs, cfg: _DecodeConfig):
    pt_ref, len_ref = refs[:2]
    rest = list(refs[2:])
    first_ref = rest.pop(0) if cfg.has_first else None
    q_ref = rest.pop(0)
    qrot_ref = cos_ref = sin_ref = None
    if cfg.has_rope:
        qrot_ref, cos_ref, sin_ref = rest.pop(0), rest.pop(0), rest.pop(0)
    k_ref, v_ref = rest.pop(0), rest.pop(0)
    ks_ref = vs_ref = None
    if cfg.has_scales:
        ks_ref, vs_ref = rest.pop(0), rest.pop(0)
    o_ref, acc_ref, m_ref, l_ref = rest

    b, hb, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    step = p
    sq, ps = cfg.sq, cfg.page_size
    ln = len_ref[b]
    if cfg.has_first:
        # the walk starts at the page that holds the first position a
        # query may see: grid step ``step`` is LOGICAL page first // ps
        # + step (the index maps turn it into a ring column)
        first = first_ref[b]
        p = first // ps + step
    # a K/V head's rows are its ``group`` query heads' sq rows each
    rows = sq * cfg.group
    native = cfg.group > 1 or cfg.has_first

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # logical pages at or past this sequence's length hold nothing this
    # query may attend to — skip their compute entirely (the decode
    # analog of the mid kernel's causal block-skip; with variable
    # lengths in a batch the grid covers the longest sequence and short
    # ones skip the difference)
    @pl.when(p * ps < ln)
    def _body():
        d = q_ref.shape[-1]
        for hi in range(cfg.block_h):
            qh = q_ref[0, hi].astype(jnp.float32)            # (sq, d)
            if cfg.has_rope:
                # q*cos + rotate_half(q)*sin: the rotation's FLOPs run
                # in-kernel under the page stream; the half-swap data
                # shuffle happened once in the wrapper (XLA fuses it
                # into the q projection epilogue)
                qh = (qh * cos_ref[0, hi].astype(jnp.float32)
                      + qrot_ref[0, hi].astype(jnp.float32)
                      * sin_ref[0, hi].astype(jnp.float32))
            qh = qh * cfg.sm_scale
            if native:
                # grouped / windowed walks hand the MXU the pages as
                # they are stored (fp32 accumulation): no per-page
                # widening pass on the VPU under a 2-FLOPs-a-byte stream
                kh, vh = k_ref[0, hi], v_ref[0, hi]
                qh = qh.astype(kh.dtype)
            else:
                kh = k_ref[0, hi].astype(jnp.float32)        # (ps, d)
                vh = v_ref[0, hi].astype(jnp.float32)
            if cfg.has_scales:
                kh = kh * jnp.repeat(
                    ks_ref[0, hi], cfg.kv_block, axis=1)[:, :d]
                vh = vh * jnp.repeat(
                    vs_ref[0, hi], cfg.kv_block, axis=1)[:, :d]
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                 # (sq, ps)
            k_pos = p * ps + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            if cfg.ancestor is not None:
                # tree verify: the last sq cache slots are the
                # candidate rows; row i sees fresh slot j iff the
                # STATIC ancestor matrix says so, plus the whole
                # committed prefix.  Each row's allowed-column set is
                # packed into an int32 bitmask selected by row iota
                # (Pallas kernels cannot capture constant arrays), so
                # the mask is sq scalar selects + one variable shift —
                # VPU work that hides under the page DMA.
                fresh = k_pos - (ln - sq)
                row = jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                bits = jnp.zeros_like(row)
                for i in range(sq):
                    rb = sum(int(cfg.ancestor[i][j]) << j
                             for j in range(sq))
                    bits = jnp.where(row == i, rb, bits)
                fr = jnp.clip(fresh, 0, sq - 1)
                tree = (jnp.right_shift(bits, fr) & 1) == 1
                mask = (fresh < 0) | (
                    (fresh >= 0) & (fresh < sq) & tree)
            elif cfg.causal:
                if cfg.group > 1:
                    # rows are (query head, token)
                    q_pos = ln - sq + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0) % sq
                else:
                    q_pos = ln - sq + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                mask = k_pos <= q_pos
            else:
                mask = k_pos < ln
            if cfg.has_first:
                mask = mask & (k_pos >= first)
            s = jnp.where(mask, s, _NEG_INF)
            r0, r1 = hi * rows, (hi + 1) * rows
            m_prev = m_ref[r0:r1, 0:1]
            l_prev = l_ref[r0:r1, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
            acc_ref[r0:r1] = acc_ref[r0:r1] * corr + jax.lax.dot_general(
                pexp.astype(vh.dtype) if native else pexp, vh,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[r0:r1] = jnp.broadcast_to(m_new, (rows, m_ref.shape[1]))
            l_ref[r0:r1] = jnp.broadcast_to(l_new, (rows, l_ref.shape[1]))

    @pl.when(step == cfg.num_pages - 1)
    def _finalize():
        # the softmax-normalization tail, fused (the operation-fusion
        # paper's point: this divide never round-trips through HBM).
        # A zero-length sequence (an idle serving slot) clamps l and
        # writes garbage the caller masks.
        ll = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / ll).reshape(o_ref.shape[1:]).astype(
            o_ref.dtype)


def _parent_pallas(q, q_rot, cos, sin, k_pages, v_pages, k_scales,
                   v_scales, page_table, lengths, cfg: _DecodeConfig,
                   first=None, name="paged_decode"):
    """``q`` (and the rope planes) come as ``(b, h_kv, group * sq, d)``:
    a K/V head's query heads are further ROWS of its program."""
    b, h, sq, d = q.shape
    ps = cfg.page_size
    nb = k_scales.shape[-1] if cfg.has_scales else 0
    bh = cfg.block_h
    n_hb = h // bh

    def qmap(bb, hb, p, *scalars):
        return (bb, hb, 0, 0)

    def kvmap(bb, hb, p, pt, ln, *fs):
        if not cfg.has_first:
            return (pt[bb, p], hb, 0, 0)
        # logical page first // ps + p, held back at the sequence's last
        # page (steps past it repeat that block: no further fetch), in
        # the ring column it lives in
        last = (jnp.maximum(ln[bb], 1) - 1) // ps
        page = jnp.minimum(fs[0][bb] // ps + p, last)
        return (pt[bb, page % cfg.table_pages], hb, 0, 0)

    in_specs = [pl.BlockSpec((1, bh, sq, d), qmap)]
    inputs = [q]
    if cfg.has_rope:
        in_specs += [pl.BlockSpec((1, bh, sq, d), qmap)] * 3
        inputs += [q_rot, cos, sin]
    in_specs += [
        pl.BlockSpec((1, bh, ps, d), kvmap),
        pl.BlockSpec((1, bh, ps, d), kvmap),
    ]
    inputs += [k_pages, v_pages]
    if cfg.has_scales:
        in_specs += [
            pl.BlockSpec((1, bh, ps, nb), kvmap),
            pl.BlockSpec((1, bh, ps, nb), kvmap),
        ]
        inputs += [k_scales, v_scales]

    scalars = [page_table.astype(jnp.int32), lengths.astype(jnp.int32)]
    if cfg.has_first:
        scalars.append(first.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, n_hb, cfg.num_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, sq, d), qmap),
        scratch_shapes=[
            pltpu.VMEM((bh * sq, d), jnp.float32),
            pltpu.VMEM((bh * sq, _LANES), jnp.float32),
            pltpu.VMEM((bh * sq, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_parent_kernel, cfg=cfg),
        grid_spec=grid_spec,
        out_shape=shape_struct((b, h, sq, d), q.dtype, q, k_pages,
                               v_pages),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=_interpret(),
        name=kernel_name(name),
    )(*scalars, *inputs)


def parent_call(q, k_pages, v_pages, page_table, lengths, *, causal=True,
                sm_scale=None, k_scales=None, v_scales=None, kv_block=128,
                rope=None, ancestor=None, num_kv_heads=None, first=None,
                max_pages=None, name="paged_decode"):
    """``fmha_decode(implementation="pallas")`` as it was through PR 33
    (the arguments it checks are taken as checked)."""
    b, h, sq, d = q.shape
    h_kv = h if num_kv_heads is None else int(num_kv_heads)
    group, width = h // h_kv, page_table.shape[1]
    rows = group * sq
    cfg = _DecodeConfig(
        sm_scale=(1.0 / d ** 0.5) if sm_scale is None else float(sm_scale),
        causal=causal, sq=sq, block_h=ad._pick_block_h(h_kv, rows),
        page_size=k_pages.shape[2],
        num_pages=(width if first is None or max_pages is None
                   else min(width, int(max_pages))),
        kv_block=int(kv_block), has_scales=k_scales is not None,
        has_rope=rope is not None,
        ancestor=None if ancestor is None else tuple(
            tuple(bool(x) for x in row) for row in ancestor),
        group=group, has_first=first is not None,
        table_pages=width if first is not None else 0)
    planes = [q]
    if rope is not None:
        planes += ad._rope_operands(q, rope)
    planes = [t.reshape(b, h_kv, rows, d) for t in planes]
    out = _parent_pallas(
        *(planes + [None] * (4 - len(planes))), k_pages, v_pages, k_scales,
        v_scales, page_table, lengths, cfg, first=first, name=name)
    return out.reshape(b, h, sq, d)


# ------------------------------------------------------------ the variants
def _column_dot(a, b, dims, preferred_element_type=None):
    """A product's shape from a broadcast: (m, k) x (n, k) or (k, n)."""
    (ca, cb), _ = dims
    n = b.shape[1 - cb[0]]
    col = a[:, :1].astype(preferred_element_type)
    return jnp.broadcast_to(col, (a.shape[0], n))


class _Over:
    """``module`` with some attributes replaced."""

    def __init__(self, module, **over):
        self._module, self._over = module, over

    def __getattr__(self, name):
        over = object.__getattribute__(self, "_over")
        if name in over:
            return over[name]
        return getattr(object.__getattribute__(self, "_module"), name)


class _NoCopy:
    def start(self):
        pass

    wait = start


STUBS = {
    "copies_stubbed": lambda: {"pltpu": _Over(
        pltpu, make_async_copy=lambda *a, **k: _NoCopy())},
    "dots_stubbed": lambda: {"jax": _Over(jax, lax=_Over(
        jax.lax, dot_general=_column_dot))},
    "heads_rolled": lambda: {"jax": _Over(jax, lax=_Over(
        jax.lax, fori_loop=lambda lo, hi, body, init, unroll=None:
        jax.lax.fori_loop(lo, hi, body, init)))},
    "softmax_stubbed": lambda: {"jnp": _Over(
        jnp, exp=lambda x: x,
        max=lambda x, axis, keepdims: x[:, :1],
        sum=lambda x, axis, keepdims: x[:, :1])},
}


@contextlib.contextmanager
def shipped_as(name, pages=None, stub=None):
    """While a call is TRACED under this, the shipped kernel carries
    ``name``, walks ``pages`` a step and has ``stub`` taken out."""
    over = {"kernel_name": lambda _: kernel_name(name)}
    if pages is not None:
        over["_pages_per_step"] = lambda *a: pages
    if stub is not None:
        over.update(STUBS[stub]())
    saved = {k: getattr(ad, k) for k in over}
    for k, v in over.items():
        setattr(ad, k, v)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(ad, k, v)


def _inputs(shape, seed):
    s = SHAPES[shape]
    rng = np.random.default_rng(seed)
    b, page, width = s["b"], s["page"], s["width"]
    parts = s["lengths"]
    # the cell's mix: each part of the batch log-uniform in its range
    lengths = np.concatenate([
        np.exp(rng.uniform(np.log(lo), np.log(hi), b // len(parts)))
        for lo, hi in parts]).astype(np.int32)
    rng.shuffle(lengths)
    lengths = np.minimum(lengths, (200 if s["window"] else width) * page)
    n_pool = 1 + b * width
    table = 1 + rng.permutation(b * width).reshape(b, width).astype(np.int32)
    mk = lambda *sh: jnp.asarray(
        rng.standard_normal(sh, np.float32)).astype(jnp.bfloat16)
    q = mk(b, s["h"], 1, s["d"])
    kp, vp = (mk(n_pool, s["h_kv"], page, s["d"]) for _ in range(2))
    first = rope = None
    if s["window"]:
        first = jnp.asarray(np.maximum(lengths - s["window"], 0))
        read = lengths - np.asarray(first) // page * page
    else:
        read = lengths
    if s["rope"]:
        ang = rng.uniform(0, 6.28, (b, 1, s["d"] // 2)).astype(np.float32)
        rope = (jnp.cos(ang), jnp.sin(ang))
    rows = int(read.sum())
    least = rows * s["h_kv"] * s["d"] * 2 * 2 / HBM_BYTES_PER_S
    kwargs = dict(num_kv_heads=s["h_kv"], first=first,
                  max_pages=s["max_pages"], rope=rope)
    return (q, kp, vp, jnp.asarray(table), jnp.asarray(lengths)), kwargs, \
        rows, least * 1e3


def _kernel_ms(trace_dir, names):
    """Durations (ms) of chip 0's Mosaic calls, by the kernel name each
    is an instruction of (an operation's name in the trace is its whole
    instruction, so a copy that READS a kernel's result names it too:
    only ``%tlm.kernel.<name>...`` at the start counts)."""
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
                        if e.name.startswith("%" + kernel_name(name)):
                            out[name].append(e.duration_ns / 1e6)
    return out


def host_stages(args, kwargs):
    """Seconds of one call's trace, lowering and backend compile."""
    f = lambda *a: ad.fmha_decode(*a, implementation="pallas", **kwargs)
    jax.jit(lambda *a: f(*a)).trace(*args).lower()     # jax's own warm-up
    t0 = time.perf_counter()
    traced = jax.jit(lambda *a: f(*a)).trace(*args)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    lowered.compile()
    t3 = time.perf_counter()
    return {"trace_s": t1 - t0, "lower_s": t2 - t1,
            "backend_compile_s": t3 - t2,
            "module_text_bytes": len(lowered.as_text())}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--pages", nargs="*", type=int, default=[1, 2, 4, 8, 16])
    ap.add_argument("--seed", type=int, default=34)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("paged_decode_ablation times a TPU; none is attached")

    result = {"device_kind": jax.devices()[0].device_kind, "shapes": {}}
    for shape in args.shapes:
        a, kwargs, rows_read, least_ms = _inputs(shape, args.seed)
        s = SHAPES[shape]
        ruled = ad._pages_per_step(
            s["page"], s["d"],
            ad._pick_block_h(s["h_kv"], s["h"] // s["h_kv"]),
            a[1].dtype.itemsize,
            min(s["width"], s["max_pages"] or s["width"]), False)
        # variant -> how the shipped kernel is traced for it
        variants = {"shipped": {}}
        # (a variant that IS the shipped program would be read from the
        # compile cache under the shipped kernel's name: left out)
        if ad._kernel_copies(s["d"], False):
            for p in args.pages:
                if p != ruled:
                    variants[f"shipped_{p}_pages"] = {"pages": p}
        for stub in STUBS:
            if stub != "copies_stubbed" or ad._kernel_copies(s["d"], False):
                variants[stub] = {"stub": stub}
        runs, refused = {}, {}

        def ready(name, call, how=None):
            """Compile ``call`` ahead of time (under ``how``), then its
            first call on the host's clock (the program's load is in
            it) and its second."""
            with contextlib.ExitStack() as stack:
                if how is not None:
                    stack.enter_context(
                        shipped_as(f"ablation.{name}.", **how))
                compiled = jax.jit(call).lower(*a).compile()
            clock = []
            for _ in range(2):
                t0 = time.perf_counter()
                out = jax.block_until_ready(compiled(*a))
                clock.append((time.perf_counter() - t0) * 1e3)
            runs[name] = {
                "call": compiled, "kernel": f"ablation.{name}.",
                "first_call_ms": clock[0], "second_call_ms": clock[1],
                "code_bytes": compiled.memory_analysis()
                .generated_code_size_in_bytes}
            return out

        ref = ready("parent", lambda *x: parent_call(
            *x, name="ablation.parent.", **kwargs))
        live = np.asarray(a[4]) > 0
        for name, how in variants.items():
            try:
                out = ready(name, lambda *x: ad.fmha_decode(
                    *x, implementation="pallas", **kwargs), how)
            except Exception as e:          # VMEM exhausted, say
                refused[name] = str(e)[:240]
                continue
            if not how.get("stub", "").endswith("_stubbed"):
                runs[name]["max_abs_diff_from_parent"] = float(jnp.max(
                    jnp.abs((out.astype(jnp.float32)
                             - ref.astype(jnp.float32))[live])))
        with tempfile.TemporaryDirectory() as tdir:
            jax.profiler.start_trace(tdir)
            for run in runs.values():
                for _ in range(args.iters):
                    out = run["call"](*a)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            ms = _kernel_ms(tdir, [r["kernel"] for r in runs.values()])
        rows = {n: {"refused": why} for n, why in refused.items()}
        for name, run in runs.items():
            durs = ms[run.pop("kernel")]
            del run["call"]
            med = statistics.median(durs) if durs else None
            rows[name] = {"ms_median": med, "calls": len(durs),
                          "share_of_least": med and least_ms / med, **run}
            print(f"{shape:20s} {name:24s} {med}  first call "
                  f"{run['first_call_ms']:.1f} ms, second "
                  f"{run['second_call_ms']:.2f}, code "
                  f"{run['code_bytes']} B", flush=True)
        result["shapes"][shape] = {
            "rows_read": rows_read, "least_ms": least_ms,
            "pages_a_step_by_rule": ruled,
            "host_stages_of_one_call": host_stages(a, kwargs),
            "variants": rows}
        print(shape, json.dumps(result["shapes"][shape]
                                ["host_stages_of_one_call"]), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
