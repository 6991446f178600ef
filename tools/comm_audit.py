"""Communication-bytes audit: compile a step, walk the HLO, and report
per-collective bytes-on-wire split by mesh axis (dcn vs ici) — plus an
OVERLAP audit of the *scheduled* HLO that proves gradient collectives
have compute to hide behind (``--overlap``).

Wall-clock DCN wins cannot be measured on the CI virtual mesh, so this
tool proves the compressed-collectives win STRUCTURALLY: it compiles
the hierarchical gradient-sync step twice (``compression=None`` vs
``compression="int8"``), walks the optimized HLO for collective ops
(all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute), classifies each by which mesh axis its
``replica_groups`` span, and totals the bytes that cross the slow dcn
axis.  The headline number is the dcn-bytes ratio (uncompressed /
compressed), gated at >= 3.5x by the multichip dryrun.

Bytes-on-wire model (per participating device, ring algorithms):

- all-reduce:       2 * (g-1)/g * operand_bytes
- all-gather:           (g-1)/g * result_bytes
- reduce-scatter:       (g-1)/g * operand_bytes
- all-to-all:           (g-1)/g * operand_bytes
- collective-permute:             operand_bytes

A collective counts toward an axis when any of its replica groups
spans more than one rank of that axis (a flat world-spanning psum
therefore counts as crossing dcn — which is exactly the traffic the
hierarchy exists to avoid).

Overlap audit (``--overlap``): the bytes model above says nothing about
whether the collective's LATENCY is exposed.  The optimized module is
scheduled (``is_scheduled=true``), so the audit walks the instruction
sequence and, per gradient collective:

- counts literal ``-start``/``-done`` async pairs and the compute
  scheduled inside each window (TPU/GPU backends emit these; the CPU
  backend used on CI executes collectives synchronously and never
  will — so zero pairs on CPU is expected, not a failure);
- computes the SCHEDULABLE overlap from dataflow: every instruction
  that is neither an ancestor of the collective's operands nor a
  descendant of its result could legally execute between start and
  done — that independent compute is exactly what a latency-hiding
  scheduler needs, and its existence is provable on any backend;
- estimates hidden vs exposed time under the ring wire model (bytes /
  per-axis bandwidth vs a FLOP/byte model of the independent compute).
  The estimate is optimistic — independent compute shared between two
  collectives is counted for both — so read it as "could hide", and
  the gate is on the overlappable FRACTION, not the milliseconds.

The overlappable FRACTION reads 1.0 for both loops on this dataflow
criterion (even the deferred reduce's late-layer collectives are
independent of earlier layers' backward, and earlier microbatches'
compute is dataflow-independent of the pipelined loop's final flush —
whether a temporal schedule can exploit that is the estimate's
optimism).  What separates the loops is the independent-compute
VOLUME: with K microbatches the pipelined loop exposes roughly (K-1)
extra whole microbatches of fwd/bwd per reduce round, so the gate
pairs overlappable_frac (sanity: no collective is dataflow-locked)
with overlap-vs-deferred ``independent_compute_ms`` (the pipelining
actually created the windows).

Run on the 8-device virtual mesh (no TPU needed):

    python tools/comm_audit.py                 # writes COMM_AUDIT.json
    python tools/comm_audit.py --ici-size 4 --block-size 256
    python tools/comm_audit.py --overlap       # writes OVERLAP_AUDIT.json
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

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


# gradient pytree shaped like a small GPT (embedding, per-layer
# attention/MLP/norms, lm head tied) — representative leaf-size mix so
# the audit exercises blocks, padding and the scale sidecar like a real
# model step would
GPT_ISH_SHAPES = {
    "embedding": (8192, 256),
    "position": (1024, 256),
    "layers": {
        "qkv_w": (4, 256, 768), "qkv_b": (4, 768),
        "proj_w": (4, 256, 256), "proj_b": (4, 256),
        "fc1_w": (4, 256, 1024), "fc1_b": (4, 1024),
        "fc2_w": (4, 1024, 256), "fc2_b": (4, 256),
        "ln1_scale": (4, 256), "ln1_bias": (4, 256),
        "ln2_scale": (4, 256), "ln2_bias": (4, 256),
    },
    "final_ln": (256,),
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[\d,{} ]*\})\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([\d,{} ]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0  # token/opaque types carry no payload
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+("
    + "|".join(_COLLECTIVES)
    + r")(-start|-done)?\((.*)$"
)


#: tlm.<phase> named scopes survive into each op's HLO metadata
#: (``op_name``), which is what lets the audit tell a ZeRO-3
#: param-gather all-gather apart from a gradient-sync one — same op,
#: same axis, different phase.
_PHASE_RE = re.compile(r"tlm\.(\w+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def parse_collectives(hlo_text: str):
    """Extract collective ops from HLO text: one record per op with
    the op kind, result/operand payload bytes, replica groups and —
    when the op carries a ``tlm.<phase>`` named scope in its metadata
    — the step phase (``param_gather`` for ZeRO-3 weight gathers,
    ``grad_sync`` for gradient reduces), beside the whole ``op_name``
    (the chain of jax scopes: a loop body, a transpose, a remat say so
    there) and the result's ``(dtype, dims)`` shapes, one per tuple
    element.  ``-done`` halves of async pairs are skipped (the
    ``-start`` op carries the payload)."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m or "%" not in line:
            continue
        if m.group(3) == "-done":
            continue
        op = m.group(2)
        pm = _PHASE_RE.search(line)
        phase = pm.group(1) if pm else None
        result_shapes = _SHAPE_RE.findall(m.group(1))
        result_bytes = sum(_shape_bytes(d, s) for d, s in result_shapes)
        nm = _OP_NAME_RE.search(line)
        # operands end at the call's closing paren; attributes
        # (replica_groups, to_apply, metadata) follow it
        operand_bytes = sum(
            _shape_bytes(d, s)
            for d, s in _SHAPE_RE.findall(m.group(4).split(")", 1)[0])
        )
        gm = _GROUPS_RE.search(line)
        groups = []
        if gm:
            groups = [
                [int(x) for x in g.split(",") if x.strip()]
                for g in re.findall(r"\{([\d, ]*)\}", gm.group(1))
            ]
        pm = _PAIRS_RE.search(line)
        pairs = []
        if pm:
            pairs = [
                tuple(int(x) for x in p.split(","))
                for p in re.findall(r"\{([\d, ]+)\}", pm.group(1))
            ]
        out.append({
            "op": op,
            "phase": phase,
            "op_name": nm.group(1) if nm else None,
            "result_shapes": [
                (d, tuple(int(x) for x in s.split(",") if x.strip()))
                for d, s in result_shapes],
            "result_bytes": result_bytes,
            "operand_bytes": operand_bytes,
            "replica_groups": groups,
            "pairs": pairs,
        })
    return out


def _wire_bytes(rec) -> float:
    # the ONE ring bytes-on-wire model, shared with the live telemetry
    # stream's per-bucket comm events (they estimate, this measures —
    # delegating keeps the two from ever drifting)
    from apex_tpu.telemetry.events import ring_wire_bytes

    g = max((len(grp) for grp in rec["replica_groups"]), default=1)
    return ring_wire_bytes(rec["op"], g, rec["operand_bytes"],
                           result_bytes=rec["result_bytes"])


def _mesh_coords(mesh, dcn_axis="dcn", ici_axis="ici"):
    """device id -> (dcn, ici) coordinate map for a mesh."""
    import numpy as np

    names = list(mesh.axis_names)
    di, ii = names.index(dcn_axis), names.index(ici_axis)
    coords = {}
    grid = np.asarray(mesh.devices)
    for idx, dev in np.ndenumerate(grid):
        coords[dev.id] = (idx[di], idx[ii])
    return coords


def _axis_label(groups, pairs, coords):
    """'dcn' | 'ici' | 'other' for a collective's replica groups."""
    groups = groups or [list(p) for p in pairs]
    crosses_dcn = crosses_ici = False
    known = True
    for grp in groups:
        cs = [coords.get(d) for d in grp]
        if any(c is None for c in cs):
            known = False
            break
        crosses_dcn |= len({c[0] for c in cs}) > 1
        crosses_ici |= len({c[1] for c in cs}) > 1
    if not known or not groups:
        return "other"
    if crosses_dcn:
        return "dcn"  # anything touching the slow axis bills dcn
    if crosses_ici:
        return "ici"
    return "other"


def classify_and_total(records, mesh, dcn_axis="dcn", ici_axis="ici"):
    """Label each collective by the mesh axes its groups span and total
    the wire bytes per label — and per LEG (``axis/op``), so the
    RS(ici) and AG(ici) halves of the hierarchical reduce are
    accounted separately from the AR(dcn) middle (the int8 gather
    compression's win lives entirely in the ici legs).  Device ids map
    to (dcn, ici) coordinates through the mesh's device grid.
    Returns ``(per_axis_totals, per_leg_totals)``."""
    coords = _mesh_coords(mesh, dcn_axis, ici_axis)
    totals = {"dcn": 0.0, "ici": 0.0, "other": 0.0}
    legs = {}
    for rec in records:
        label = _axis_label(rec["replica_groups"], rec["pairs"], coords)
        wb = _wire_bytes(rec)
        rec["axis"] = label
        rec["wire_bytes"] = wb
        totals[label] += wb
        leg = f"{label}/{rec['op']}"
        legs[leg] = legs.get(leg, 0.0) + wb
    return totals, legs


def audit_fn(jitted, args, mesh, dcn_axis="dcn", ici_axis="ici"):
    """Compile ``jitted`` for ``args``, walk the optimized HLO and
    return ``(per_axis_totals, per_leg_totals, collective_records)``."""
    txt = jitted.lower(*args).compile().as_text()
    records = parse_collectives(txt)
    totals, legs = classify_and_total(records, mesh, dcn_axis, ici_axis)
    return totals, legs, records


def audit_gradient_sync(compression, ici_size=4, block_size=256,
                        shapes=GPT_ISH_SHAPES, dtype=None):
    """Compile the hierarchical gradient-sync step over a GPT-shaped
    grad pytree and audit its collectives.  Returns the result dict."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.ops.quantization import CompressionConfig
    from apex_tpu.parallel import (
        all_reduce_gradients,
        hierarchical_data_parallel_mesh,
    )
    from apex_tpu.parallel.distributed import (
        comm_state_specs,
        init_comm_state,
    )

    dtype = dtype or jnp.float32
    mesh = hierarchical_data_parallel_mesh(ici_size=ici_size)
    axes = ("dcn", "ici")
    grads = jax.tree.map(
        lambda s: jnp.zeros(s, dtype), shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    pspec = jax.tree.map(lambda _: P(), grads)

    if isinstance(compression, CompressionConfig):
        cfg = compression
        compression = cfg.method + ("+ici" if cfg.ici_legs else "")
    elif compression is not None:
        cfg = CompressionConfig(method=compression,
                                block_size=block_size)
    else:
        cfg = None

    if cfg is not None and cfg.error_feedback:
        cstate = init_comm_state(grads, axes, cfg, mesh=mesh)
        cspecs = comm_state_specs(cstate, axes)
        fn = jax.shard_map(
            lambda g, st: all_reduce_gradients(
                g, axes, compression=cfg, comm_state=st),
            mesh=mesh, in_specs=(pspec, cspecs),
            out_specs=(pspec, cspecs),
        )
        args = (grads, cstate)
    else:
        fn = jax.shard_map(
            lambda g: all_reduce_gradients(g, axes, compression=cfg),
            mesh=mesh, in_specs=(pspec,), out_specs=pspec,
        )
        args = (grads,)

    totals, legs, records = audit_fn(jax.jit(fn), args, mesh)
    n_elems = sum(
        int(jnp.size(l)) for l in jax.tree.leaves(grads)
    )
    return {
        "compression": compression or "none",
        "ici_size": ici_size,
        "block_size": cfg.block_size if cfg is not None else block_size,
        "grad_elements": n_elems,
        "grad_bytes": n_elems * jnp.dtype(dtype).itemsize,
        "bytes_on_wire": {k: round(v, 1) for k, v in totals.items()},
        "bytes_by_leg": {k: round(v, 1) for k, v in sorted(legs.items())},
        "collectives": [
            {"op": r["op"], "axis": r["axis"],
             "wire_bytes": round(r["wire_bytes"], 1)}
            for r in records
        ],
    }


def run_audit(ici_size=4, block_size=256):
    """The before/after TRIPLE + reduction ratios: compression=None,
    DCN-only int8 (the headline ``value`` stays the dcn ratio for
    record continuity), and int8 with ``ici_legs=True`` (the EQuARX
    gather-leg half) with per-LEG compressed-vs-full ratios — the
    number the multichip dryrun's ici config gates at >= 3x."""
    from apex_tpu.ops.quantization import (
        CompressionConfig as _CC,
    )

    base = audit_gradient_sync(None, ici_size, block_size)
    comp = audit_gradient_sync("int8", ici_size, block_size)
    gather = audit_gradient_sync(
        _CC(block_size=block_size, ici_legs=True), ici_size, block_size
    )
    ratio = (base["bytes_on_wire"]["dcn"]
             / max(comp["bytes_on_wire"]["dcn"], 1e-9))
    ici_ratio = (base["bytes_on_wire"]["ici"]
                 / max(gather["bytes_on_wire"]["ici"], 1e-9))
    # SEMANTIC leg pairing, not name matching: the compressed RS
    # lowers as an int8 all-to-all and the compressed dcn all-reduce
    # as all-to-all + all-gather, so a same-key comparison would
    # silently drop the reduce-scatter leg (the largest one) from the
    # report
    bl, gl = base["bytes_by_leg"], gather["bytes_by_leg"]

    def _ratio(base_bytes, comp_bytes):
        return round(base_bytes / comp_bytes, 2) if comp_bytes else None

    leg_ratios = {
        "rs_ici": _ratio(bl.get("ici/reduce-scatter", 0.0),
                         gl.get("ici/all-to-all", 0.0)),
        "ag_ici": _ratio(bl.get("ici/all-gather", 0.0),
                         gl.get("ici/all-gather", 0.0)),
        "ar_dcn": _ratio(bl.get("dcn/all-reduce", 0.0),
                         gl.get("dcn/all-to-all", 0.0)
                         + gl.get("dcn/all-gather", 0.0)),
    }
    return {
        "metric": "dcn_gradient_bytes_ratio",
        "value": round(ratio, 2),
        "unit": "x fewer dcn bytes (int8 vs none)",
        "ici_gather_ratio": round(ici_ratio, 2),
        "ici_gather_ratio_unit": "x fewer ici bytes (int8 ici_legs "
                                 "vs none, RS+AG legs)",
        "leg_ratios_vs_gather_compressed": leg_ratios,
        "baseline": base,
        "compressed": comp,
        "gather_compressed": gather,
    }


def phase_leg_totals(records):
    """Wire-byte totals keyed ``phase/axis/op`` (phase ``other`` when
    the op carries no ``tlm.*`` scope) — the view that separates the
    ZeRO-3 param-gather legs from the gradient legs.  Call after
    :func:`classify_and_total` (it stamps ``axis``/``wire_bytes``)."""
    out = {}
    for r in records:
        key = f"{r.get('phase') or 'other'}/{r['axis']}/{r['op']}"
        out[key] = out.get(key, 0.0) + r["wire_bytes"]
    return {k: round(v, 1) for k, v in sorted(out.items())}


def audit_zero3_step(compression, ici_size=4, block_size=256,
                     bucket_kb=64, shapes=GPT_ISH_SHAPES):
    """Compile one ZeRO-3 train step (gather-on-use → grads → RS into
    the shard → sharded update) over a GPT-shaped param pytree and
    audit its collectives, split param-AG vs grad legs by the
    ``tlm.param_gather`` / ``tlm.grad_sync`` HLO metadata."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.ops.quantization import CompressionConfig
    from apex_tpu.parallel import hierarchical_data_parallel_mesh

    mesh = hierarchical_data_parallel_mesh(ici_size=ici_size)
    axes = ("dcn", "ici")
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    if isinstance(compression, str):
        compression = CompressionConfig(method=compression,
                                        block_size=block_size,
                                        error_feedback=False)
    opt = DistributedFusedAdam(
        lr=1e-2, axis_name=axes, shard_params=True,
        bucket_bytes=bucket_kb * 1024, compression=compression)
    layout = opt.build_layout(params, mesh=mesh)
    pspec = jax.tree.map(lambda _: P(), params)
    sspec, stspec = opt.shard_spec(), opt.state_specs()

    def step(sh, st, g):
        p, st = opt.gather_params(sh, st)
        # grads must DEPEND on the gathered weights or DCE folds the
        # gather away; + 0*p is free and keeps the dataflow honest
        g = jax.tree.map(lambda gi, pi: gi + 0.0 * pi, g, p)
        return opt.step(st, g, sh)

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(sspec, stspec, pspec),
        out_specs=(sspec, stspec),
    ))
    sh = jax.ShapeDtypeStruct(
        (ici_size * layout.shard_size,), jnp.float32)
    st = {"step": jax.ShapeDtypeStruct((), jnp.int32),
          "exp_avg": sh, "exp_avg_sq": sh}
    totals, legs, records = audit_fn(fn, (sh, st, params), mesh)
    phases = phase_leg_totals(records)
    param_ag = sum(v for k, v in phases.items()
                   if k.startswith("param_gather/"))
    grad = sum(v for k, v in phases.items()
               if k.startswith("grad_sync/"))
    cfg = compression
    return {
        "compression": ("none" if cfg is None else
                        cfg.method + ("+ici" if cfg.ici_legs else "")),
        "ici_size": ici_size,
        "bucket_kb": bucket_kb,
        "shard_elements": layout.shard_size,
        "bytes_on_wire": {k: round(v, 1) for k, v in totals.items()},
        "bytes_by_phase_leg": phases,
        "param_ag_wire_bytes": round(param_ag, 1),
        "grad_wire_bytes": round(grad, 1),
    }


def run_zero3_audit(ici_size=4, block_size=256, bucket_kb=64):
    """The ZeRO-3 before/after pair: full-width param gathers vs int8
    (``ici_legs=True``) ones, with the headline ``value`` the param-AG
    wire-bytes ratio the multichip dryrun's zero3 config gates at
    ≥ 3x, plus the grad-leg ratio for completeness (the grads ride the
    same chunk-preserving int8 legs as the DDP path)."""
    from apex_tpu.ops.quantization import CompressionConfig as _CC

    base = audit_zero3_step(None, ici_size, block_size, bucket_kb)
    comp = audit_zero3_step(
        _CC(block_size=block_size, ici_legs=True,
            error_feedback=False),
        ici_size, block_size, bucket_kb)
    ratio = (base["param_ag_wire_bytes"]
             / max(comp["param_ag_wire_bytes"], 1e-9))
    grad_ratio = (base["grad_wire_bytes"]
                  / max(comp["grad_wire_bytes"], 1e-9))
    return {
        "metric": "zero3_param_ag_bytes_ratio",
        "value": round(ratio, 2),
        "unit": "x fewer param-AG wire bytes (int8 ici_legs vs "
                "full-width model dtype)",
        "grad_leg_ratio": round(grad_ratio, 2),
        "baseline": base,
        "gather_compressed": comp,
    }


# ------------------------------------------------------------------ overlap
#
# Ring wire model extended with time: per-axis bandwidth for collective
# duration, peak FLOP/s + HBM bandwidth for the compute that could hide
# it.  v4-ish defaults; the gate uses fractions, not absolute ms.
WIRE_MODEL = {
    "flops": 275e12,      # peak bf16 FLOP/s per chip
    "hbm_bytes_s": 1.2e12,
    "dcn_bytes_s": 25e9,  # per-device DCN bandwidth
    "ici_bytes_s": 90e9,  # per-device ICI bandwidth
}

# ops with no meaningful execution cost for the overlap estimate
_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id",
}

_COMP_HDR_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$"
)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*((?:\([^=]*?\)|\S+))\s+"
    r"([\w\-]+)\("
)


def _shape_elems(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n


def _call_args(rest: str) -> str:
    """The operand list of ``op(...)``: everything up to the paren that
    closes the call (operand TYPES may nest parens for tuples)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


def parse_instructions(hlo_text: str):
    """Parse the (scheduled) HLO text into per-computation instruction
    lists, each entry in program order with name, op, payload sizes,
    operand names and — for collectives — replica groups."""
    comps = {}
    cur = None
    for line in hlo_text.splitlines():
        hm = _COMP_HDR_RE.match(line)
        if hm:
            cur = hm.group(2)
            comps[cur] = []
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            if line.strip() == "}":
                cur = None
            continue
        name, result, op = m.group(1), m.group(2), m.group(3)
        rest = line[m.end():]
        args = _call_args(rest)
        operands = re.findall(r"(?<!=)%([\w\.\-]+)", args)
        op_shapes = _SHAPE_RE.findall(args)
        res_shapes = _SHAPE_RE.findall(result)
        gm = _GROUPS_RE.search(line)
        groups = []
        if gm:
            groups = [
                [int(x) for x in g.split(",") if x.strip()]
                for g in re.findall(r"\{([\d, ]*)\}", gm.group(1))
            ]
        pm = _PAIRS_RE.search(line)
        pairs = []
        if pm:
            pairs = [
                tuple(int(x) for x in p.split(","))
                for p in re.findall(r"\{([\d, ]+)\}", pm.group(1))
            ]
        comps[cur].append({
            "name": name,
            "op": op,
            "operands": operands,
            "result_bytes": sum(_shape_bytes(d, s) for d, s in res_shapes),
            "result_elems": sum(_shape_elems(d, s) for d, s in res_shapes),
            "operand_bytes": sum(_shape_bytes(d, s) for d, s in op_shapes),
            "operand_elems": [_shape_elems(d, s) for d, s in op_shapes],
            "replica_groups": groups,
            "pairs": pairs,
        })
    return comps


def _base_collective(op: str):
    for c in _COLLECTIVES:
        if op == c or op == c + "-start" or op == c + "-done":
            return c
    return None


def _compute_time_s(rec, model=WIRE_MODEL) -> float:
    """Rough execution-time estimate for one (non-collective)
    instruction: dots by a FLOP model (contracted extent inferred from
    the element counts), everything else memory-bound."""
    op = rec["op"]
    if op in _FREE_OPS or _base_collective(op):
        return 0.0
    if op in ("dot", "convolution"):
        res = max(rec["result_elems"], 1)
        ops = rec["operand_elems"]
        if len(ops) >= 2 and ops[0] and ops[1]:
            k = (ops[0] * ops[1] / res) ** 0.5
        else:
            k = 1.0
        return 2.0 * res * max(k, 1.0) / model["flops"]
    return (rec["result_bytes"] + rec["operand_bytes"]) \
        / model["hbm_bytes_s"]


def _collective_time_s(rec, label, model=WIRE_MODEL) -> float:
    wb = _wire_bytes(rec)
    bw = model["dcn_bytes_s"] if label == "dcn" else model["ici_bytes_s"]
    return wb / bw


def analyze_overlap(hlo_text: str, mesh=None, dcn_axis="dcn",
                    ici_axis="ici", model=WIRE_MODEL):
    """Walk every computation of a SCHEDULED module and, for each
    collective, measure what a latency-hiding scheduler can put between
    its start and done:

    - async ``-start``/``-done`` pairs: the compute actually scheduled
      inside the window (the backend already committed to the overlap);
    - synchronous collectives: the compute that is dataflow-INDEPENDENT
      of the collective (neither ancestor nor descendant) — legal to
      schedule inside the window, i.e. the structural overlap a
      latency-hiding backend can exploit.

    Returns ``(per_collective_records, summary)``."""
    comps = parse_instructions(hlo_text)
    coords = _mesh_coords(mesh, dcn_axis, ici_axis) if mesh else None
    out = []
    for cname, instrs in comps.items():
        index = {r["name"]: i for i, r in enumerate(instrs)}
        deps = [
            [index[o] for o in r["operands"] if o in index]
            for r in instrs
        ]
        users = [[] for _ in instrs]
        for i, ds in enumerate(deps):
            for d in ds:
                users[d].append(i)

        def closure(start_idx, edges):
            seen = set()
            todo = list(edges[start_idx])
            while todo:
                j = todo.pop()
                if j in seen:
                    continue
                seen.add(j)
                todo.extend(edges[j])
            return seen

        for i, r in enumerate(instrs):
            base = _base_collective(r["op"])
            if base is None or r["op"].endswith("-done"):
                continue
            is_start = r["op"].endswith("-start")
            rec = {
                "computation": cname,
                "op": base,
                "name": r["name"],
                "async_pair": False,
                "result_bytes": r["result_bytes"],
                "operand_bytes": r["operand_bytes"],
                "replica_groups": r["replica_groups"],
                "pairs": r["pairs"],
            }
            if is_start:
                done = next(
                    (j for j in range(i + 1, len(instrs))
                     if instrs[j]["op"] == base + "-done"
                     and r["name"] in instrs[j]["operands"]),
                    None,
                )
                rec["async_pair"] = done is not None
                window = instrs[i + 1:done] if done is not None else []
                hidden = sum(_compute_time_s(w, model) for w in window)
            else:
                anc = closure(i, deps)
                desc = closure(i, users)
                excluded = anc | desc | {i}
                hidden = sum(
                    _compute_time_s(w, model)
                    for j, w in enumerate(instrs)
                    if j not in excluded
                )
            label = (_axis_label(r["replica_groups"], r["pairs"], coords)
                     if coords else "other")
            t = _collective_time_s(rec, label, model)
            rec.update({
                "axis": label,
                "wire_bytes": round(_wire_bytes(rec), 1),
                "collective_s": t,
                "hidden_s": min(hidden, t),
                "independent_compute_s": hidden,
                "exposed_s": max(0.0, t - hidden),
                "overlappable": hidden > 0.0,
            })
            out.append(rec)
    coll = sum(r["collective_s"] for r in out)
    hidden = sum(r["hidden_s"] for r in out)
    exposed = sum(r["exposed_s"] for r in out)
    indep = sum(r["independent_compute_s"] for r in out)
    n = len(out)
    summary = {
        "n_collectives": n,
        "n_async_pairs": sum(1 for r in out if r["async_pair"]),
        "n_overlappable": sum(1 for r in out if r["overlappable"]),
        "overlappable_frac": round(
            sum(1 for r in out if r["overlappable"]) / n, 3
        ) if n else 0.0,
        "collective_ms": round(coll * 1e3, 4),
        "hidden_ms": round(hidden * 1e3, 4),
        "exposed_ms": round(exposed * 1e3, 4),
        "hidden_frac": round(hidden / coll, 3) if coll else 0.0,
        # how much compute each collective could hide behind, on
        # average — the number that separates the pipelined loop
        # (whole microbatches of independent fwd/bwd per round) from
        # the deferred one (only the last backward's tail)
        "independent_compute_ms": round(indep * 1e3, 4),
        "mean_independent_compute_ms_per_collective": round(
            indep / n * 1e3, 5
        ) if n else 0.0,
    }
    for ax in ("dcn", "ici"):
        rs = [r for r in out if r["axis"] == ax]
        summary[f"{ax}_collectives"] = len(rs)
        summary[f"{ax}_overlappable"] = sum(
            1 for r in rs if r["overlappable"]
        )
    return out, summary


# MLP proxy for the audited accumulation loop: per-layer leaves so the
# reverse-order bucket assembly has real structure, matmul fwd/bwd so
# the "independent compute" the analysis finds is genuine dot work
_OVERLAP_LAYERS = 4
_OVERLAP_WIDTH = 128


def _overlap_params(key=0):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(key),
                          2 * _OVERLAP_LAYERS + 1)
    p = {}
    for l in range(_OVERLAP_LAYERS):
        p[f"l{l}"] = {
            "w": 0.1 * jax.random.normal(
                ks[2 * l], (_OVERLAP_WIDTH, _OVERLAP_WIDTH)),
            "b": jnp.zeros((_OVERLAP_WIDTH,)),
        }
    p["head"] = 0.1 * jax.random.normal(
        ks[-1], (_OVERLAP_WIDTH, 2 * _OVERLAP_WIDTH))
    return p


def _overlap_loss(p, x):
    import jax.numpy as jnp

    h = x
    for l in range(_OVERLAP_LAYERS):
        h = jnp.tanh(h @ p[f"l{l}"]["w"] + p[f"l{l}"]["b"])
    z = h @ p["head"]
    return jnp.sum(z * z) / z.size


def compile_grad_sync_loop(overlap, compression=None, ici_size=4,
                           bucket_bytes=96 * 1024, num_micro=3,
                           rows=16):
    """Compile the K-microbatch accumulate-and-reduce loop (pipelined
    when ``overlap``, the deferred seed pattern otherwise) and return
    ``(scheduled_hlo_text, mesh)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel import hierarchical_data_parallel_mesh
    from apex_tpu.parallel.distributed import Reducer

    mesh = hierarchical_data_parallel_mesh(ici_size=ici_size)
    params = _overlap_params()
    red = Reducer(
        axis_name=("dcn", "ici"), overlap_grad_sync=overlap,
        bucket_bytes=bucket_bytes, compression=compression,
    )

    def step(p, batch):
        acc = red.init(p)
        for k in range(num_micro):
            g = jax.grad(_overlap_loss)(p, batch[k])
            acc = red.accumulate(acc, g)
        grads, _ = red.reduce(acc)
        return grads

    pspec = jax.tree.map(lambda _: P(), params)
    data = jnp.zeros(
        (num_micro, rows * mesh.devices.size, _OVERLAP_WIDTH)
    )
    fn = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, P(None, ("dcn", "ici"))),
        out_specs=pspec,
    ))
    txt = fn.lower(params, data).compile().as_text()
    return txt, mesh


def run_overlap_audit(ici_size=4, bucket_kb=96, num_micro=3):
    """Overlapped vs deferred grad sync through the scheduled-HLO
    analysis, plus the int8-compressed overlapped variant.  The
    headline value is the overlapped loop's overlappable fraction
    (sanity gate: every grad collective has SOME independent compute);
    the discriminating number is independent_compute_ms overlap vs
    deferred — pipelining adds ~(K-1) microbatches of hideable
    compute per round (see the module docstring)."""
    results = {}
    for tag, overlap, comp in (
        ("overlap", True, None),
        ("deferred", False, None),
        ("overlap_int8", True, "int8"),
    ):
        txt, mesh = compile_grad_sync_loop(
            overlap, comp, ici_size=ici_size,
            bucket_bytes=bucket_kb * 1024, num_micro=num_micro,
        )
        records, summary = analyze_overlap(txt, mesh)
        results[tag] = {
            "summary": summary,
            "collectives": [
                {k: rec[k] for k in (
                    "op", "axis", "wire_bytes", "overlappable",
                    "async_pair")}
                for rec in records
            ],
        }
    return {
        "metric": "grad_sync_overlappable_fraction",
        "value": results["overlap"]["summary"]["overlappable_frac"],
        "unit": "fraction of grad collectives with independent compute "
                "to hide behind (pipelined loop)",
        "num_micro": num_micro,
        "bucket_kb": bucket_kb,
        "ici_size": ici_size,
        "wire_model": WIRE_MODEL,
        **results,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ici-size", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=256)
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual device count when no backend is up")
    ap.add_argument("--min-ratio", type=float, default=None,
                    help="exit nonzero unless the dcn-bytes ratio "
                         "meets this floor")
    ap.add_argument("--overlap", action="store_true",
                    help="audit the scheduled HLO of the pipelined "
                         "accumulate-and-reduce loop instead of the "
                         "bytes A/B (writes OVERLAP_AUDIT.json)")
    ap.add_argument("--zero3", action="store_true",
                    help="audit the ZeRO-3 gather-on-use step instead: "
                         "param-AG vs grad legs split by phase "
                         "metadata, full-width vs int8 gathers "
                         "(writes ZERO3_AUDIT.json)")
    ap.add_argument("--num-micro", type=int, default=3)
    ap.add_argument("--bucket-kb", type=int, default=96)
    ap.add_argument("--min-overlappable", type=float, default=None,
                    help="with --overlap: exit nonzero unless the "
                         "overlappable fraction meets this floor")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    _force_virtual_devices(args.devices)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if args.zero3:
        out_path = args.out or os.path.join(root, "ZERO3_AUDIT.json")
        doc = run_zero3_audit(args.ici_size, args.block_size)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps({
            "metric": doc["metric"], "value": doc["value"],
            "unit": doc["unit"],
            "grad_leg_ratio": doc["grad_leg_ratio"],
            "param_ag_bytes_none":
                doc["baseline"]["param_ag_wire_bytes"],
            "param_ag_bytes_int8":
                doc["gather_compressed"]["param_ag_wire_bytes"],
        }))
        print(f"wrote {out_path}")
        if args.min_ratio is not None and doc["value"] < args.min_ratio:
            raise SystemExit(
                f"param-AG bytes ratio {doc['value']} < floor "
                f"{args.min_ratio}"
            )
        return
    if args.overlap:
        out_path = args.out or os.path.join(root, "OVERLAP_AUDIT.json")
        doc = run_overlap_audit(args.ici_size, args.bucket_kb,
                                args.num_micro)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps({
            "metric": doc["metric"], "value": doc["value"],
            "unit": doc["unit"],
            "overlap": doc["overlap"]["summary"],
            "deferred": doc["deferred"]["summary"],
            "overlap_int8": doc["overlap_int8"]["summary"],
        }))
        print(f"wrote {out_path}")
        if (args.min_overlappable is not None
                and doc["value"] < args.min_overlappable):
            raise SystemExit(
                f"overlappable fraction {doc['value']} < floor "
                f"{args.min_overlappable}"
            )
        return

    args.out = args.out or os.path.join(root, "COMM_AUDIT.json")
    doc = run_audit(args.ici_size, args.block_size)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({
        "metric": doc["metric"], "value": doc["value"],
        "unit": doc["unit"],
        "dcn_bytes_none": doc["baseline"]["bytes_on_wire"]["dcn"],
        "dcn_bytes_int8": doc["compressed"]["bytes_on_wire"]["dcn"],
        "ici_bytes_none": doc["baseline"]["bytes_on_wire"]["ici"],
        "ici_bytes_int8": doc["compressed"]["bytes_on_wire"]["ici"],
    }))
    print(f"wrote {args.out}")
    if args.min_ratio is not None and doc["value"] < args.min_ratio:
        raise SystemExit(
            f"dcn bytes ratio {doc['value']} < floor {args.min_ratio}"
        )


if __name__ == "__main__":
    main()
