"""Grouped expert product: a SwiGLU over row tiles that each belong to
one expert, every expert's weights copied once.

``HeldExpertsMLP`` lays the (token, choice) pairs it computes out in
tiles of ``T`` rows, a tile one expert's, consecutive tiles of one
expert side by side.  Its XLA loop slices a tile's expert out of the
stack and multiplies: at a prefill chunk's rows an expert that reads
22 MB for every 128 rows (128 FLOPs a weight byte where a v5e's ridge is
240).  Here the same tiles are the INNER axis of a Mosaic grid whose
outer axis walks blocks of output columns, the contraction whole in a
block:

- ``gate_up``: grid (f / tn, tiles); a step multiplies its ``(T, h)``
  rows by its expert's ``(h, tn)`` blocks of ``w_gate`` and ``w_up`` and
  writes ``silu(a) * b`` rounded to the weights' dtype;
- ``down``: grid (h / tn', tiles); the ``(T, f)`` rows of that by the
  expert's ``(f, tn')`` block of ``w_down``.

The rows and the results are pipelined by their block specs.  The
weights stay where they are (the 4-D stack ``(layer, expert, ...)`` is
indexed through prefetched scalars, no layer's experts are cut out of
it) and the kernel copies a block itself, once a RUN — the consecutive
tiles of one expert — into one of two buffers, a run ahead: a run's
first tile starts the next run's copy, which has the whole run to land.
(With the weights under block specs the pipeline fetches an expert's
block one TILE ahead, 18 us of copy under 10 us of products at Xing4's
widths: 3.6-3.8 ms a layer against 3.0-3.4, PERF.md section 6, PR 38.)
Tiles past the live count run no body and ask for the rows of the last
live tile, which are resident.  Operands go to the MXU as they are
stored, products accumulate in float32:
:meth:`HeldExpertsMLP._swiglu`'s arithmetic in its precisions.

jax ships the general design (``pallas/ops/tpu/megablox/gmm.py``: groups
that end inside a tile, a blocked contraction); it neither fuses the
SwiGLU nor indexes a layer stack.  docs/models.md "Grouped computation";
``tools/moe_grouped_ablation.py`` times it beside the loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.attention import _interpret
from apex_tpu.ops.common import largest_tile, shape_struct
from apex_tpu.telemetry.spans import kernel_name

__all__ = ["grouped_swiglu", "MOE_GROUPED_BLOCK_BYTES",
           "MOE_GROUPED_VMEM_LIMIT"]

_LANES = 128
#: the most one weight block may hold: the contraction whole times as
#: many output columns as fit (Xing4: an expert's whole ``(3584, 1024)``
#: matrix; DeepSeek-V3.2's ``(7168, 2048)``: 512 columns of it)
MOE_GROUPED_BLOCK_BYTES = 8 * 1024 * 1024
#: ``gate_up`` keeps two buffers of two matrices' blocks (32 MiB at the
#: most) beside its rows and its float32 products: over Mosaic's default
#: scoped limit of 16 MiB, inside a v5e's 128 MiB
MOE_GROUPED_VMEM_LIMIT = 64 * 1024 * 1024


def _columns(width: int, contraction: int, itemsize: int,
             block_bytes: int) -> int:
    """Output columns a block: as many as keep ``contraction`` rows of
    them within ``block_bytes`` (a width that is no multiple of 128 goes
    whole)."""
    if width % _LANES:
        return width
    return largest_tile(width, block_bytes // (contraction * itemsize))


def _kernel(plan_ref, meta_ref, x_ref, *refs, act, tn):
    """A tile's rows by its expert's blocks.  A run's blocks are in the
    buffer the plan names (runs alternate); its first tile starts the
    copies of the run after it and waits for its own."""
    n = (len(refs) - 2) // 2
    w_hbm, o_ref, bufs, sem = refs[:n], refs[n], refs[n + 1:-1], refs[-1]
    j, t = pl.program_id(0), pl.program_id(1)
    layer = meta_ref[1]

    def copies(e, slot):
        cols = pl.ds(pl.multiple_of(j * tn, tn), tn)
        return [pltpu.make_async_copy(
            w.at[layer, e, :, cols], buf.at[slot], sem.at[i, slot])
            for i, (w, buf) in enumerate(zip(w_hbm, bufs))]

    @pl.when(t < meta_ref[0])
    def _body():
        e, slot, after = plan_ref[0, t], plan_ref[1, t], plan_ref[3, t]

        @pl.when(t == 0)
        def _first_run():
            for copy in copies(e, slot):
                copy.start()

        @pl.when(plan_ref[2, t] == 1)
        def _run_starts():
            @pl.when(after >= 0)
            def _():
                for copy in copies(after, 1 - slot):
                    copy.start()
            for copy in copies(e, slot):
                copy.wait()

        x = x_ref[...]
        o_ref[...] = act(*(
            jnp.dot(x, buf[slot], preferred_element_type=jnp.float32)
            for buf in bufs)).astype(o_ref.dtype)


def _product(act, rows, weights, T, tn, interpret):
    """``act`` of ``rows`` (tiles * T, c) by the ``(c, tn)`` blocks of
    each of ``weights`` (layers, experts, c, width) -> (tiles * T,
    width): the kernel and its ``pallas_call``'s keyword arguments, all
    but the name (a call site names its own call)."""
    c, width = weights[0].shape[2:]
    tiles = rows.shape[0] // T

    def tile(t, meta):
        # a step that runs no body asks for the rows it holds
        return jnp.minimum(t, meta[0] - 1)

    return functools.partial(_kernel, act=act, tn=tn), dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(width // tn, tiles),
            in_specs=[pl.BlockSpec(
                (T, c), lambda j, t, plan, meta: (tile(t, meta), 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(weights),
            out_specs=pl.BlockSpec(
                (T, tn), lambda j, t, plan, meta: (tile(t, meta), j)),
            scratch_shapes=[pltpu.VMEM((2, c, tn), w.dtype) for w in weights]
            + [pltpu.SemaphoreType.DMA((len(weights), 2))],
        ),
        out_shape=shape_struct((rows.shape[0], width), rows.dtype, rows,
                               *weights),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=MOE_GROUPED_VMEM_LIMIT),
        interpret=interpret,
    )


def _run_plan(tile_expert, live):
    """(4, tiles), by tile: its expert; the buffer of its run (a RUN is
    the consecutive tiles of one expert; runs alternate); whether it is
    its run's first; the expert of the run after, -1 past the live
    tiles."""
    tiles = tile_expert.shape[0]
    at = jnp.arange(tiles, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             tile_expert[1:] != tile_expert[:-1]])
    slot = (jnp.cumsum(first, dtype=jnp.int32) - 1) % 2
    # the first tile of the run after: the nearest run start past t
    after = jnp.concatenate([
        jax.lax.cummin(jnp.where(first, at, tiles)[1:], reverse=True),
        jnp.full((1,), tiles, jnp.int32)])
    after_expert = jnp.where(
        after < live, tile_expert[jnp.minimum(after, tiles - 1)], -1)
    return jnp.stack([tile_expert, slot, first.astype(jnp.int32),
                      after_expert])


def _grouped_swiglu(rows, w_gate, w_up, w_down, tile_expert, live, layer,
                    block_bytes, interpret):
    T = rows.shape[0] // tile_expert.shape[0]
    h, f = w_gate.shape[2:]
    size = w_gate.dtype.itemsize
    plan = _run_plan(tile_expert.astype(jnp.int32), live)
    meta = jnp.stack([live, layer]).astype(jnp.int32)
    kernel, call = _product(lambda a, b: jax.nn.silu(a) * b, rows,
                            (w_gate, w_up), T,
                            _columns(f, h, size, block_bytes), interpret)
    act = pl.pallas_call(
        kernel, **call, name=kernel_name("moe_grouped.gate_up"),
    )(plan, meta, rows, w_gate, w_up)
    kernel, call = _product(lambda a: a, act, (w_down,), T,
                            _columns(h, f, size, block_bytes), interpret)
    return pl.pallas_call(
        kernel, **call, name=kernel_name("moe_grouped.down"),
    )(plan, meta, act, w_down)


# a program's expert layers make this call at the same shapes: as a
# jitted function it is traced and lowered ONCE a program (ROADMAP S10);
# the block size and interpret mode are arguments, so that a trace made
# off the TPU is not found again by a compile for one
_grouped_swiglu_once = jax.jit(_grouped_swiglu, static_argnums=(7, 8))


def grouped_swiglu(rows, w_gate, w_up, w_down, tile_expert, live, layer=0):
    """``rows`` (tiles * T, h) in the weights' dtype, tile ``t`` the rows
    of expert ``tile_expert[t]`` (tiles,), one expert's tiles side by
    side, the first ``live`` tiles in use (at least 1; a traced scalar)
    -> ``(silu(rows w_gate) * (rows w_up)) w_down`` of each tile's
    expert, (tiles * T, h) in that dtype.  Rows of tiles past ``live``
    come back UNWRITTEN.

    ``w_gate`` / ``w_up`` (layers, experts, h, f) and ``w_down``
    (layers, experts, f, h) are layer stacks and ``layer`` (a traced
    scalar is fine) says whose experts these are; without the layer
    axis they are one layer's.  ``T`` a multiple of 16 and ``h``, ``f``
    multiples of 128 on a TPU; anything in interpret mode."""
    if w_gate.ndim == 3:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
    return _grouped_swiglu_once(
        rows, w_gate, w_up, w_down, tile_expert, jnp.asarray(live),
        jnp.asarray(layer), MOE_GROUPED_BLOCK_BYTES, _interpret())
