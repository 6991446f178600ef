"""The ledger of programs (``apex_tpu/telemetry/programs.py``): one
record per executable jax obtains, by name and stage.

Driven two ways: with real tiny jits, and with synthetic events through
jax's public ``jax.monitoring.record_*`` (known durations, so the
nesting rules are checked against exact sums).  The ledger is the
process's, so every case reads ``records_from(count)`` of its own start
and traces functions whose names no other test uses.

Then the two places that read it: the claims of the modules that build
the main path's programs (every ``*_jit`` of each served model's
``GPTDecodeFns``, the trainer's step), and ``ContinuousBatcher.pump``,
which says on its span and in two counters when a turn recompiled.
"""

import glob
import importlib
import importlib.util
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import chip_smoke
from apex_tpu.serving.kv_cache import KVCacheConfig, PagedKVCache, init_pools
from apex_tpu.serving.serve import ContinuousBatcher, Request
from apex_tpu.telemetry import events, programs
from apex_tpu.telemetry.programs import (
    CACHE_HIT_EVENT, CACHE_MISS_EVENT, CACHE_RETRIEVAL_EVENT, LOWER_EVENT,
    OBTAIN_EVENT, TRACE_EVENT, ledger,
)
from apex_tpu.transformer import parallel_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _named(records, name):
    return [r for r in records if r.name == name]


# ---------------------------------------------------------------------------
# real tiny jits
# ---------------------------------------------------------------------------
def test_one_record_per_executable_with_all_three_stages():
    def _ledger_toy_stages(x):
        return jnp.tanh(x) * 2 + 1

    n0, t0 = ledger.count, time.perf_counter()
    jax.jit(_ledger_toy_stages)(jnp.ones(4))
    (rec,) = _named(ledger.records_from(n0), "_ledger_toy_stages")
    assert rec.seq == 1 and rec.cache == "off" and rec.retrieval_s == 0.0
    assert rec.trace_s > 0 and rec.lower_s > 0 and rec.obtain_s > 0
    assert rec.traced_inside >= 1           # jnp.tanh is a jitted function
    assert t0 <= rec.t_begin < rec.t_end <= time.perf_counter()
    assert rec.t_end - rec.t_begin >= 0.9 * rec.total_s
    assert ledger.count == n0 + len(ledger.records_from(n0))
    assert rec in ledger.records(since=t0) and \
        rec not in ledger.records(until=t0)


def test_same_shapes_add_none_and_a_new_shape_adds_seq_2():
    def _ledger_toy_shapes(x):
        return x * 3

    f = jax.jit(_ledger_toy_shapes)
    n0 = ledger.count
    f(jnp.ones(4))
    n1, total1 = ledger.count, ledger.obtain_s_total
    f(jnp.ones(4))
    assert (ledger.count, ledger.obtain_s_total) == (n1, total1)
    f(jnp.ones(8))
    recs = _named(ledger.records_from(n0), "_ledger_toy_shapes")
    assert [r.seq for r in recs] == [1, 2]
    assert ledger.obtain_s_total == pytest.approx(
        total1 + sum(r.obtain_s for r in ledger.records_from(n1)))


def test_a_jitted_function_inside_another_is_counted_not_timed_twice():
    @jax.jit
    def _ledger_toy_inner(x):
        return jnp.sin(x) + 1

    def _ledger_toy_outer(x):
        return _ledger_toy_inner(x) * _ledger_toy_inner(x + 1)

    n0, t0 = ledger.count, time.perf_counter()
    jax.jit(_ledger_toy_outer)(jnp.ones(4))
    wall = time.perf_counter() - t0
    recs = ledger.records_from(n0)
    (outer,) = _named(recs, "_ledger_toy_outer")
    assert _named(recs, "_ledger_toy_inner") == []   # inlined: no executable
    assert outer.traced_inside >= 2     # inner (once or twice) and its ops
    # the stages of everything obtained fit the wall clock once
    assert sum(r.total_s for r in recs) <= wall


def test_a_trace_that_obtains_nothing_makes_no_record():
    def _ledger_toy_shape_only(x):
        return x + 1

    f = jax.jit(_ledger_toy_shape_only)
    n0 = ledger.count
    jax.eval_shape(f, jnp.ones(3))
    lowered = f.lower(jnp.ones(5))
    assert _named(ledger.records_from(n0), "_ledger_toy_shape_only") == []
    lowered.compile()
    (rec,) = _named(ledger.records_from(n0), "_ledger_toy_shape_only")
    assert rec.trace_s > 0 and rec.lower_s > 0 and rec.obtain_s > 0


@pytest.fixture()
def persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], -1)
    try:
        yield
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_persistent_cache_reads_miss_then_hit(persistent_cache):
    def _ledger_toy_cached(x):
        return jnp.cos(x) * 3

    f = jax.jit(_ledger_toy_cached)
    n0 = ledger.count
    f(jnp.ones(5))
    jax.clear_caches()                  # jax's in-memory executables
    f(jnp.ones(5))
    first, second = _named(ledger.records_from(n0), "_ledger_toy_cached")
    assert (first.cache, first.retrieval_s) == ("miss", 0.0)
    assert second.cache == "hit" and second.seq == 2
    assert 0 < second.retrieval_s <= second.obtain_s
    assert second.trace_s > 0 and second.lower_s > 0    # cold or warm


def test_installing_twice_records_once_and_uninstall_stops():
    def _ledger_toy_once(x):
        return x - 1

    from apex_tpu.telemetry import programs as again

    assert again is programs and \
        importlib.import_module("apex_tpu.telemetry.programs") is programs
    reloaded = importlib.reload(programs)
    assert reloaded.ledger is ledger    # one a process, whatever imports
    programs.install()
    programs.install()
    f = jax.jit(_ledger_toy_once)
    n0 = ledger.count
    f(jnp.ones(2))
    assert len(_named(ledger.records_from(n0), "_ledger_toy_once")) == 1
    programs.uninstall()
    try:
        f(jnp.ones(3))
        assert len(_named(ledger.records_from(n0), "_ledger_toy_once")) == 1
    finally:
        programs.install()
    f(jnp.ones(6))
    assert [r.seq for r in _named(ledger.records_from(n0),
                                  "_ledger_toy_once")] == [1, 2]


class _Sink:
    def __init__(self):
        self.seen = []

    def event(self, kind, **fields):
        self.seen.append((kind, fields))


def test_program_obtained_reaches_a_sink_and_nothing_is_built_without(
        monkeypatch):
    def _ledger_toy_event(x):
        return x * x

    f = jax.jit(_ledger_toy_event)
    sink = _Sink()
    with events.sink(sink):
        f(jnp.ones(4))
    (fields,) = [f_ for kind, f_ in sink.seen
                 if kind == "program_obtained"
                 and f_["name"] == "_ledger_toy_event"]
    assert set(fields) == {"name", "seq", "trace_s", "lower_s", "obtain_s",
                           "cache", "traced_inside"}
    assert fields["seq"] == 1 and fields["cache"] == "off"
    assert fields["obtain_s"] > 0
    # no sink: the ledger does not reach the bus at all
    calls = []
    monkeypatch.setattr(events, "emit",
                        lambda *a, **k: calls.append((a, k)))
    n0 = ledger.count
    f(jnp.ones(9))
    assert ledger.count > n0 and calls == []


def test_appends_are_safe_from_any_thread():
    def work(i):
        def _ledger_toy_thread(x):
            return x + i

        f = jax.jit(_ledger_toy_thread)
        for n in (2, 3, 4):
            f(jnp.ones((n, i + 1)))

    n0 = ledger.count
    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = _named(ledger.records_from(n0), "_ledger_toy_thread")
    assert sorted(r.seq for r in recs) == list(range(1, 13))
    assert all(r.trace_s > 0 and r.lower_s > 0 for r in recs)
    assert ledger.count - n0 == len(ledger.records_from(n0))


def test_compile_clock_of_the_smoke_test_is_a_view_of_the_ledger():
    def _ledger_toy_clock(x):
        return x / 2

    f = jax.jit(_ledger_toy_clock)
    f(jnp.ones(2))                      # before the clock: not its
    clock = chip_smoke.CompileClock()
    assert clock.total == 0.0 and not clock.times
    f(jnp.ones(3))
    assert clock.times["jit(_ledger_toy_clock)"] == 1
    assert clock.total == pytest.approx(
        sum(r.obtain_s for r in ledger.records_from(clock._count0)))


# ---------------------------------------------------------------------------
# synthetic events, through jax's public recorders
# ---------------------------------------------------------------------------
def _begin(event, name):
    jax.monitoring.record_scalar(event, time.time(), fun_name=name)


def _end(event, name, seconds):
    jax.monitoring.record_event_duration_secs(event, seconds, fun_name=name)


def _stage(event, name, seconds):
    _begin(event, name)
    _end(event, name, seconds)


def test_nested_trace_seconds_are_never_added_to_the_caller_s():
    n0 = ledger.count
    _begin(TRACE_EVENT, "_syn_outer")
    _stage(TRACE_EVENT, "_syn_leaf", 5.0)
    _begin(TRACE_EVENT, "_syn_mid")
    _stage(TRACE_EVENT, "_syn_leaf", 1.0)
    _end(TRACE_EVENT, "_syn_mid", 2.0)
    _end(TRACE_EVENT, "_syn_outer", 9.0)
    _stage(LOWER_EVENT, "jit(_syn_outer)", 1.5)
    _stage(OBTAIN_EVENT, "jit(_syn_outer)", 4.0)
    (rec,) = ledger.records_from(n0)
    assert (rec.name, rec.trace_s, rec.traced_inside, rec.lower_s,
            rec.obtain_s, rec.cache) == ("_syn_outer", 9.0, 3, 1.5, 4.0,
                                         "off")
    assert rec.total_s == 14.5


def test_a_program_obtained_inside_a_trace_gets_its_own_record():
    """An eager operation on concrete values inside a traced body: its
    lowering and obtaining come out of the caller's trace seconds."""
    n0 = ledger.count
    _begin(TRACE_EVENT, "_syn_body")
    _stage(TRACE_EVENT, "_syn_eager", 0.25)
    _stage(LOWER_EVENT, "jit(_syn_eager)", 0.5)
    _stage(OBTAIN_EVENT, "jit(_syn_eager)", 1.0)
    _end(TRACE_EVENT, "_syn_body", 4.0)
    _stage(LOWER_EVENT, "jit(_syn_body)", 2.0)
    _stage(OBTAIN_EVENT, "jit(_syn_body)", 3.0)
    eager, body = ledger.records_from(n0)
    assert (eager.name, eager.trace_s, eager.lower_s, eager.obtain_s) == \
        ("_syn_eager", 0.0, 0.5, 1.0)
    assert (body.name, body.trace_s, body.traced_inside, body.lower_s,
            body.obtain_s) == ("_syn_body", 2.5, 1, 2.0, 3.0)
    assert eager.total_s + body.total_s == 9.0   # the wall, once


def test_a_lowering_rule_s_own_traces_do_not_displace_the_program():
    n0 = ledger.count
    _stage(TRACE_EVENT, "_syn_ruled", 1.0)
    _begin(LOWER_EVENT, "jit(_syn_ruled)")
    _stage(TRACE_EVENT, "_syn_rule_helper", 0.125)  # mlir.lower_fun
    _end(LOWER_EVENT, "jit(_syn_ruled)", 0.75)
    _stage(OBTAIN_EVENT, "jit(_syn_ruled)", 2.0)
    (rec,) = ledger.records_from(n0)
    assert (rec.name, rec.trace_s, rec.lower_s, rec.obtain_s) == \
        ("_syn_ruled", 1.0, 0.75, 2.0)


def test_a_stale_trace_is_not_given_to_another_program():
    n0 = ledger.count
    _stage(TRACE_EVENT, "_syn_shape_only", 7.0)        # an eval_shape
    _stage(LOWER_EVENT, "jit(_syn_cached_trace)", 0.5)  # jax had its trace
    _stage(OBTAIN_EVENT, "jit(_syn_cached_trace)", 1.0)
    (rec,) = ledger.records_from(n0)
    assert (rec.name, rec.trace_s, rec.traced_inside, rec.lower_s) == \
        ("_syn_cached_trace", 0.0, 0, 0.5)
    assert rec.t_end - rec.t_begin == pytest.approx(1.5)


def test_cache_events_belong_to_the_open_backend_compile():
    n0 = ledger.count
    jax.monitoring.record_event(CACHE_HIT_EVENT)    # nothing open: dropped
    _stage(TRACE_EVENT, "_syn_hit", 1.0)
    _stage(LOWER_EVENT, "jit(_syn_hit)", 1.0)
    _begin(OBTAIN_EVENT, "jit(_syn_hit)")
    jax.monitoring.record_event(CACHE_HIT_EVENT)
    jax.monitoring.record_event_duration_secs(CACHE_RETRIEVAL_EVENT, 0.25)
    _end(OBTAIN_EVENT, "jit(_syn_hit)", 0.5)
    _begin(OBTAIN_EVENT, "jit(_syn_miss)")
    jax.monitoring.record_event(CACHE_MISS_EVENT)
    _end(OBTAIN_EVENT, "jit(_syn_miss)", 30.0)
    _stage(OBTAIN_EVENT, "jit(_syn_off)", 2.0)
    hit, miss, off = ledger.records_from(n0)
    assert (hit.cache, hit.retrieval_s) == ("hit", 0.25)
    assert (miss.cache, miss.retrieval_s) == ("miss", 0.0)
    assert (off.cache, off.retrieval_s) == ("off", 0.0)


def test_table_groups_by_name_sorts_by_seconds_and_says_whose():
    t0 = time.perf_counter()
    for seconds in (1.0, 2.0):
        _stage(TRACE_EVENT, "_syn_table_own", seconds)
        _stage(LOWER_EVENT, "jit(_syn_table_own)", 0.5)
        _stage(OBTAIN_EVENT, "jit(_syn_table_own)", 0.25)
    _stage(OBTAIN_EVENT, "jit(_syn_table_big)", 60.0)
    _stage(OBTAIN_EVENT, "jit(_syn_table_small)", 0.125)
    programs.own("_syn_table_own", layer="a test's layer")
    assert programs.layer_of("_syn_table_own") == "a test's layer"
    assert programs.layer_of("_syn_table_big") is None
    lines = programs.table(since=t0).splitlines()
    assert lines[0].split() == ["program", "exec", "trace_s", "inside",
                                "lower_s", "obtain_s", "hit/miss", "layer"]
    assert [l.split()[0] for l in lines[1:]] == [
        "_syn_table_big", "_syn_table_own", "_syn_table_small"]
    assert lines[2].split()[1:6] == ["2", "3.000", "0", "1.000", "0.500"]
    assert lines[2].endswith("a test's layer") and \
        lines[1].endswith("other")
    folded = programs.table(since=t0, top=1).splitlines()
    assert len(folded) == 3 and folded[2].startswith("(2 more)")
    assert folded[2].split()[2:4] == ["3", "3.000"]


# ---------------------------------------------------------------------------
# the claims: no program of the main path falls silently into "other"
# ---------------------------------------------------------------------------
def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


def _gpt_fns():
    from apex_tpu.models import GPTConfig, GPTModel

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    try:
        model = GPTModel(GPTConfig(
            vocab_size=64, num_layers=2, hidden_size=32,
            num_attention_heads=4, max_position_embeddings=64,
            compute_dtype=jnp.float32, remat=False, attention_impl="xla"))
        params = model.init(jax.random.PRNGKey(0))
        ccfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8, num_pages=11,
            page_size=4, max_seqs=2, pages_per_seq=5, dtype=jnp.float32)
        chain = model.decode_fns(params, mesh, ccfg, max_prompt_len=12,
                                 prefill_chunk=4, speculate_k=2)
        tree = model.decode_fns(params, mesh, ccfg, max_prompt_len=12,
                                speculate_k=2, spec_tree=(-1, 0, 0, 1))
    finally:
        parallel_state.destroy_model_parallel()
    return [chain, tree]


_LATENT_HF = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_shared_experts=1,
    rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"))
PAGE, CHUNK, PAGES_PER_SEQ, SLOTS = 4, 8, 8, 2


def _latent_stack(model, cfg, index_dim):
    mesh = _one_device_mesh()
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            NamedSharding(mesh, P()))
    ccfg = KVCacheConfig(
        num_layers=3, num_heads=1, head_dim=cfg.latent_dim,
        num_pages=1 + SLOTS * PAGES_PER_SEQ, page_size=PAGE, max_seqs=SLOTS,
        pages_per_seq=PAGES_PER_SEQ, dtype=jnp.float32, kind="latent",
        latent_dim=cfg.latent_dim, index_dim=index_dim)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=24,
                           prefill_chunk=CHUNK)
    return {"mesh": mesh, "ccfg": ccfg, "fns": fns}


def _dsv32_stack():
    from apex_tpu.models.deepseek_v32 import (
        DeepSeekV32Config, DeepSeekV32Model,
    )

    cfg = DeepSeekV32Config.from_hf(
        dict(_LATENT_HF, index_n_heads=4, index_head_dim=16, index_topk=8,
             num_experts_per_tok=4, n_group=4, topk_group=2,
             routed_scaling_factor=2.5),
        n_routed_experts=16, held_experts=(1, 4, 6, 11),
        params_dtype=jnp.float32)
    return _latent_stack(DeepSeekV32Model(cfg), cfg, cfg.index_head_dim)


def _xing4_fns():
    from apex_tpu.models.xing4 import Xing4Config, Xing4Model

    cfg = Xing4Config.from_hf(
        dict(_LATENT_HF, n_routed_experts=8, num_experts_per_tok=2,
             n_group=1, topk_group=1, routed_scaling_factor=2.0, hc_mult=4,
             hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
             mhc_h_res_clamp_max=30),
        params_dtype=jnp.float32)
    return [_latent_stack(Xing4Model(cfg), cfg, 0)["fns"]]


def _afmoe_fns():
    from apex_tpu.models.afmoe import AfmoeConfig, AfmoeModel

    hf = dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=3,
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts_per_tok=2, num_shared_experts=1, route_scale=2.448,
        rms_norm_eps=1e-5, rope_theta=10000.0, mup_enabled=True,
        sliding_window=8,
        layer_types=["sliding_attention", "sliding_attention",
                     "full_attention"])
    cfg = AfmoeConfig.from_hf(hf, num_experts=16, held_experts=(1, 4, 6, 11),
                              params_dtype=jnp.float32)
    model = AfmoeModel(cfg)
    mesh = _one_device_mesh()
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            NamedSharding(mesh, P()))
    ccfg = KVCacheConfig.of_classes(
        model.cache_classes(slots=SLOTS, pages_per_seq=PAGES_PER_SEQ,
                            page_size=PAGE, prefill_chunk=CHUNK),
        page_size=PAGE, max_seqs=SLOTS, dtype=jnp.float32)
    return [model.decode_fns(params, mesh, ccfg, max_prompt_len=24,
                             prefill_chunk=CHUNK)]


@pytest.mark.parametrize("build", [
    _gpt_fns, lambda: [_dsv32_stack()["fns"]], _afmoe_fns, _xing4_fns],
    ids=["gpt", "deepseek_v32", "afmoe", "xing4"])
def test_every_jit_of_a_served_model_carries_an_owned_name(build):
    seen = set()
    for fns in build():
        jits = {k: v for k, v in vars(fns).items()
                if k.endswith("_jit") and v is not None}
        assert {"prefill_jit", "decode_jit"} <= set(jits)
        for field, jitted in jits.items():
            assert programs.layer_of(jitted.__name__) == "serving steps", \
                f"{field} traces {jitted.__name__!r}, which nobody claimed"
            seen.add(jitted.__name__)
    assert "_decode" in seen and seen <= {
        "_prefill", "_chunk", "_decode", "_spec", "_spec_tree"}


def test_the_batcher_s_and_the_trainer_s_programs_are_claimed(tmp_path):
    for name in ("copy_pages", "import_pages", "_import_state"):
        assert programs.layer_of(name) == "serving entry"
    spec = importlib.util.spec_from_file_location(
        "gpt_pretrain", os.path.join(REPO, "examples", "gpt_pretrain.py"))
    trainer = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trainer)
    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    n0 = ledger.count
    try:
        out = trainer.main([
            "--tp", "2", "--layers", "2", "--hidden", "64", "--heads", "4",
            "--seq", "32", "--vocab", "256", "--opt-level", "O0",
            "--micro-batch", "1", "--num-micro", "1", "--steps", "2",
            "--log-every", "1000000"])
    finally:
        parallel_state.destroy_model_parallel()
    assert out["step"].__name__ == "train_step"
    (rec,) = _named(ledger.records_from(n0), "train_step")
    assert programs.layer_of(rec.name) == "train step"
    assert rec.trace_s > 0 and rec.traced_inside > 10


# ---------------------------------------------------------------------------
# the pump says when it recompiled
# ---------------------------------------------------------------------------
def _pump_spans(directory):
    (path,) = glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.start_ns, dict(e.stats)) for e in line.events
                    if e.name == "tlm.serve.pump"]
    return [stats for _, stats in sorted(out, key=lambda p: p[0])]


def _serve(stack, prompt_tokens, uid):
    b = ContinuousBatcher(
        stack["fns"].prefill, stack["fns"].decode,
        PagedKVCache(stack["ccfg"]),
        jax.device_put(init_pools(stack["ccfg"]),
                       NamedSharding(stack["mesh"], P())),
        max_prompt_len=24, harvest_every=2, chunk_fn=stack["fns"].chunk,
        prefill_chunk=CHUNK)
    prompt = [int(t) for t in
              np.random.default_rng(7).integers(1, 96, prompt_tokens)]
    done = b.run([Request(uid=uid, prompt=prompt, max_new_tokens=4)])
    assert len(done[uid].tokens) == 4
    return b


@pytest.fixture(scope="module")
def recompiling_server(tmp_path_factory):
    """A latent-attention server whose chunk program has one executable
    a context extent.  Warmed on a one-chunk prompt (extent 8); then,
    inside a profiler session, a two-chunk prompt (extent 16 is new) and
    the same once more (warm)."""
    stack = _dsv32_stack()
    n_warm = ledger.count
    warmed = _serve(stack, 6, "warm")
    warm_obtained = ledger.records_from(n_warm)
    directory = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        n0 = ledger.count
        cold = _serve(stack, 14, "cold")
        n1 = ledger.count
        again = _serve(stack, 14, "again")
        n2 = ledger.count
    finally:
        jax.profiler.stop_trace()
    spans = _pump_spans(directory)
    assert len(spans) == cold.turns + again.turns
    return {"warmed": warmed, "cold": cold, "again": again,
            "warm_obtained": warm_obtained,
            "cold_spans": spans[:cold.turns],
            "again_spans": spans[cold.turns:],
            "obtained": ledger.records_from(n0)[:n1 - n0],
            "obtained_again": n2 - n1}


def test_a_turn_that_meets_a_new_shape_says_so(recompiling_server):
    run = recompiling_server
    b, spans = run["cold"], run["cold_spans"]
    said = [s for s in spans if "executables" in s]
    assert len(said) == b.compiled_turns >= 1
    assert said[-1]["turn"] == b.last_compiled_turn
    chunk_turns = [s for s in said
                   if "_chunk" in s["obtained"].split(",")]
    assert len(chunk_turns) == 1        # extent 16, and only it
    assert sum(s["executables"] for s in said) == len(run["obtained"])
    assert sum(s["obtain_us"] for s in said) == pytest.approx(
        1e6 * sum(r.obtain_s for r in run["obtained"]), abs=len(said))
    for s in said:
        assert s["executables"] >= 1 and s["obtain_us"] > 0
        assert set(s["obtained"].split(",")) <= {
            r.name for r in run["obtained"]}
    (rec,) = _named(run["obtained"], "_chunk")
    # the executable after the warm-up's (the process's second where
    # this file runs first: the ledger counts a name's over the process)
    (warm,) = _named(run["warm_obtained"], "_chunk")
    assert rec.seq == warm.seq + 1
    assert programs.layer_of("_chunk") == "serving steps"
    # the turns that obtained nothing carry none of the three
    for s in spans:
        if s not in said:
            assert not {"executables", "obtain_us", "obtained"} & set(s)


def test_a_warm_turn_says_nothing(recompiling_server):
    run = recompiling_server
    assert run["obtained_again"] == 0
    b = run["again"]
    assert b.turns >= 2
    assert (b.compiled_turns, b.last_compiled_turn) == (0, None)
    for s in run["again_spans"]:
        assert not {"executables", "obtain_us", "obtained"} & set(s)
        assert {"turn", "queued", "live_slots"} <= set(s)
