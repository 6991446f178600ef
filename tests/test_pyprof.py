"""Profiling subsystem tests."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from apex_tpu.pyprof import Timers, annotate, cost_analysis, summarize


def test_annotate_preserves_semantics():
    @annotate
    def f(x):
        return x * 2

    np.testing.assert_allclose(np.asarray(f(jnp.ones(3))), 2.0)
    np.testing.assert_allclose(
        np.asarray(jax.jit(f)(jnp.ones(3))), 2.0
    )


def test_annotate_names_hlo():
    @annotate(name="my_region")
    def f(x):
        return jnp.sin(x) + 1

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "my_region" in text


def test_cost_analysis_matmul_flops():
    def f(a, b):
        return a @ b

    a = jnp.ones((64, 64))
    costs = cost_analysis(f, a, a)
    # 2*M*N*K = 524288 flops for a 64^3 matmul
    assert costs.get("flops", 0) >= 2 * 64**3 * 0.9


def test_summarize_roofline():
    def f(a, b):
        return a @ b

    a = jnp.ones((128, 128))
    rep = summarize(f, a, a, peak_flops=1e12, peak_bandwidth=1e11)
    assert rep["flops"] > 0
    assert "compute_bound" in rep and "min_time_s" in rep
    assert rep["arithmetic_intensity"] > 0


def test_timers():
    timers = Timers()
    t = timers("fwd")
    t.start()
    x = jnp.ones((256, 256)) @ jnp.ones((256, 256))
    t.stop(barrier_on=x)
    assert timers("fwd").elapsed(reset=False) > 0
    log = timers.log()
    assert "fwd" in log
    # start/stop state machine guards
    t2 = timers("bwd")
    t2.start()
    try:
        t2.start()
        raised = False
    except AssertionError:
        raised = True
    assert raised


def test_parse_per_op_table(tmp_path):
    """Trace a jitted step, parse the xplane file into per-op rows
    (reference: apex/pyprof/parse/parse.py -> prof per-op tables)."""
    from apex_tpu.pyprof import op_table, parse, trace

    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w).sum()

    x = jnp.ones((128, 128))
    w = jnp.ones((128, 128))
    jax.block_until_ready(step(x, w))  # compile outside the trace
    log_dir = str(tmp_path / "trace")
    with trace(log_dir):
        for _ in range(3):
            jax.block_until_ready(step(x, w))

    rows = parse(log_dir)
    assert rows, "parse returned no rows"
    names = " ".join(r["name"] for r in rows)
    # the dot kernel must show up as a device event
    assert "dot" in names or "tanh" in names, names[:500]
    r0 = rows[0]
    assert r0["count"] >= 1 and r0["total_ms"] > 0
    assert abs(sum(r["pct"] for r in rows) - 100.0) < 1e-6
    # repeated events aggregate: some op should have count >= 3
    assert any(r["count"] >= 3 for r in rows)
    table = op_table(rows)
    assert "total ms" in table and rows[0]["name"][:20] in table


def test_parse_missing_dir_raises(tmp_path):
    from apex_tpu.pyprof import parse

    with pytest.raises(FileNotFoundError):
        parse(str(tmp_path / "nope"))


def test_classify_op_classes():
    """HLO names land in the reference-taxonomy op classes
    (reference: apex/pyprof/prof/ 27 op-class modules)."""
    from apex_tpu.pyprof import classify

    assert classify("%dot.12") == ("gemm", "compute")
    assert classify("fusion.3")[0] == "fusion"
    assert classify("while.2")[0] == "loop_control"
    assert classify("%copy-start.5 = (bf16[8,8,1024,128]{3,2,1,0}, u32[]{})")[0] == "copy_layout"
    assert classify("convert.9")[0] == "copy_layout"
    assert classify("all-reduce.1") == ("all_reduce", "collective")
    assert classify("collective-permute.7")[1] == "collective"
    assert classify("copy.2") == ("copy_layout", "memory")
    assert classify("convolution.4")[0] == "convolution"
    assert classify("flash_attention_fwd")[0] == "flash_attention"
    assert classify("threefry2x32")[0] == "rng"
    assert classify("mystery_kernel_xyz") == ("other", "other")


def test_prof_class_report(tmp_path):
    """parse → prof → per-class table with time-by-kind split
    (reference: python -m apex.pyprof.prof)."""
    from apex_tpu.pyprof import parse, prof, prof_table, trace

    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w).sum()

    x = jnp.ones((128, 128))
    w = jnp.ones((128, 128))
    jax.block_until_ready(step(x, w))
    log_dir = str(tmp_path / "trace")
    with trace(log_dir):
        for _ in range(3):
            jax.block_until_ready(step(x, w))

    classes = prof(parse(log_dir))
    assert classes, "prof returned no classes"
    by_name = {r["op_class"]: r for r in classes}
    # a matmul step must produce gemm (or fused) compute time
    assert "gemm" in by_name or "fusion" in by_name
    for r in classes:
        assert r["count"] >= 1 and r["total_ms"] >= 0 and r["ops"]
    assert abs(sum(r["pct"] for r in classes) - 100.0) < 1e-6
    table = prof_table(classes)
    assert "time by kind" in table and "class" in table


def test_trace_region_nesting(tmp_path):
    """Nested trace_region scopes — the pieces the telemetry
    TraceTrigger + phase spans reuse — compose: inner/outer names both
    land in the compiled HLO metadata, and the host-side annotation
    stack unwinds cleanly inside an active xplane capture."""
    from apex_tpu.pyprof import trace, trace_region

    def f(x):
        with trace_region("outer"):
            y = x @ x
            with trace_region("inner"):
                y = jnp.tanh(y)
        return y.sum()

    lowered = jax.jit(f).lower(jnp.ones((16, 16)))
    text = lowered.as_text(debug_info=True)
    assert "outer" in text and "inner" in text
    # named scopes nest: the inner op's metadata carries BOTH scopes
    assert "outer/inner" in text

    # host side: nested regions inside a live capture neither raise nor
    # leave the annotation stack unbalanced (a second capture works)
    x = jnp.ones((16, 16))
    jf = jax.jit(f)
    jax.block_until_ready(jf(x))
    for round_ in ("t1", "t2"):
        with trace(str(tmp_path / round_)):
            with trace_region("outer"):
                with trace_region("inner"):
                    jax.block_until_ready(jf(x))
        assert (tmp_path / round_).is_dir()


def test_cost_analysis_sharded_mesh_function():
    """cost_analysis on a shard_map'd (mesh) function — the sharded
    path the telemetry StepStats MFU model sits on top of; the seed
    suite only exercised single-device cost analysis."""
    from apex_tpu.pyprof import cost_analysis, summarize
    from apex_tpu.transformer import parallel_state
    from jax.sharding import PartitionSpec as P

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel()
    try:
        dp = mesh.shape["dp"]
        N = 64

        def local_step(w, x):
            y = jnp.tanh(x @ w)
            return jax.lax.pmean(jnp.sum(y * y) / y.size, "dp")

        fn = jax.shard_map(local_step, mesh=mesh,
                       in_specs=(P(), P("dp")), out_specs=P())
        w = jnp.ones((N, N))
        x = jnp.ones((8 * dp, N))
        costs = cost_analysis(fn, w, x)
        # the dominant matmul's flops must be visible through the
        # sharded lowering.  XLA's cost model prices the PER-DEVICE
        # program: 8 local rows x N x N, not the global batch —
        # multiply by device count for machine-scale numbers
        local_flops = 2 * 8 * N * N
        assert costs.get("flops", 0) >= local_flops * 0.9
        assert costs.get("flops", 0) < local_flops * dp
        rep = summarize(fn, w, x, peak_flops=1e12, peak_bandwidth=1e11)
        assert rep["flops"] > 0 and rep["bytes_accessed"] > 0
        assert "min_time_s" in rep
    finally:
        parallel_state.destroy_model_parallel()


def test_utilization_report(tmp_path):
    """trace -> prof -> utilization with cost analysis: the reference
    prof stage's FLOPs/efficiency columns (apex/pyprof/prof/)."""
    from apex_tpu.pyprof import cost_analysis, parse, prof, trace, utilization

    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w).sum()

    x = jnp.ones((256, 256))
    w = jnp.ones((256, 256))
    jax.block_until_ready(step(x, w))
    log_dir = str(tmp_path / "trace")
    steps = 4
    with trace(log_dir):
        for _ in range(steps):
            jax.block_until_ready(step(x, w))
    classes = prof(parse(log_dir))
    costs = cost_analysis(step, x, w)
    rep = utilization(classes, costs, peak_flops=1e12, steps=steps)
    assert rep["flops"] >= 2 * 256**3 * 0.9
    assert rep["compute_ms"] >= 0 and rep["achieved_flops_per_sec"] >= 0
    if rep["compute_ms"] > 0:
        assert "compute_utilization" in rep
