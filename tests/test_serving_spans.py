"""The program's own trace channel (``apex_tpu/telemetry/spans.py``):
host spans inside ``ContinuousBatcher``, phases inside the compiled
serving steps, Mosaic kernels found by name.

One CPU profiler session is shared by every case (module-scoped
fixture): a tiny two-slot batcher serves three requests on the plain,
the chunked and the speculative path inside it, and once more on the
plain path BEFORE it (no session), so that:

- the spans nest as docs/observability.md "Serving spans" says, and
  their numbers are the scheduler's own (``dispatch_decode`` = steps
  run, ``dispatch_prefill`` = admissions / chunks, one ``first_token``
  per request, ``commit`` tokens = tokens generated);
- with no session nothing is recorded and the tokens are the same;
- the compiled serving programs carry ``tlm.prefill`` / ``tlm.decode``
  and a train step's compiled text names its attention kernels, a
  rematerialised forward (``nothing_saveable``) apart;
- ``Request.arrival_s`` becomes ``Completion.queue_wait_s`` and the
  ``queue_wait_us`` stat of the request's ``dispatch_prefill`` span.
"""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.serving.kv_cache import (
    KVCacheConfig,
    PagedKVCache,
    init_pools,
)
from apex_tpu.serving.serve import ContinuousBatcher, Request, init_carry
from apex_tpu.telemetry.spans import KERNEL_PREFIX, host_span, kernel_name

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, NEW, MAXP, SLOTS, CHUNK, K = 4, 6, 12, 2, 4, 2
PREFIX = "tlm.serve."


# ---------------------------------------------------------------------------
# the tiny serving stack
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def stack():
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    model = GPTModel(GPTConfig(
        vocab_size=64, num_layers=2, hidden_size=32,
        num_attention_heads=4, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat=False, attention_impl="xla"))
    params = model.init(jax.random.PRNGKey(0))
    pps = -(-(MAXP + NEW) // PAGE)
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=4, head_dim=8,
        num_pages=1 + SLOTS * pps, page_size=PAGE, max_seqs=SLOTS,
        pages_per_seq=pps, dtype=jnp.float32)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=MAXP,
                           prefill_chunk=CHUNK, speculate_k=K)
    # repetitive prompts, so the n-gram drafter has something to draft
    rng = np.random.RandomState(3)
    prompts = [[int(t) for t in np.tile(rng.randint(1, 64, (4,)), 3)[:n]]
               for n in (12, 9, 6)]
    yield {"mesh": mesh, "model": model, "params": params, "ccfg": ccfg,
           "fns": fns, "prompts": prompts}
    parallel_state.destroy_model_parallel()


def _batcher(stack, path: str) -> ContinuousBatcher:
    fns, ccfg = stack["fns"], stack["ccfg"]
    kw = {"chunked": dict(chunk_fn=fns.chunk, prefill_chunk=CHUNK),
          "speculative": dict(spec_fn=fns.spec, speculate_k=K),
          "plain": {}}[path]
    return ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), init_pools(ccfg),
        max_prompt_len=MAXP, harvest_every=3, **kw)


def _requests(stack, **kw):
    return [Request(uid=f"r{i}", prompt=list(p), max_new_tokens=NEW, **kw)
            for i, p in enumerate(stack["prompts"])]


# ---------------------------------------------------------------------------
# one profiler session for the module
# ---------------------------------------------------------------------------
class Span:
    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end = name, start, end
        self.stats, self.parent = stats, None

    def __repr__(self):
        return f"<{self.name} {self.stats}>"


def _read_spans(directory):
    """Every ``tlm.*`` host event of the trace, parents resolved by
    nesting on the one thread that wrote them."""
    (path,) = glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb"))
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats))
                      for e in line.events if e.name.startswith("tlm.")]
    spans.sort(key=lambda s: (s.start, -s.end))
    stack = []
    for s in spans:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        s.parent = stack[-1] if stack else None
        stack.append(s)
    return spans


@pytest.fixture(scope="module")
def session(stack, tmp_path_factory):
    """path -> {"batcher", "completions", "spans"}; "untraced" ran
    before the session began."""
    out = {}
    b = _batcher(stack, "plain")
    out["untraced"] = {"batcher": b,
                       "completions": dict(b.run(_requests(stack)))}
    directory = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        for path in ("plain", "chunked", "speculative"):
            b = _batcher(stack, path)
            t0 = time.perf_counter()
            with host_span("test." + path):
                done = b.run(_requests(
                    stack, arrival_s=t0 - 0.25 if path == "plain" else None))
            out[path] = {"batcher": b, "completions": dict(done)}
    finally:
        jax.profiler.stop_trace()
    spans = _read_spans(directory)
    for path in ("plain", "chunked", "speculative"):
        (root,) = [s for s in spans if s.name == "tlm.test." + path]
        out[path]["spans"] = [
            s for s in spans if s.name.startswith(PREFIX)
            and root.start <= s.start and s.end <= root.end]
    out["all"] = spans
    return out


def _named(run, name):
    return [s for s in run["spans"] if s.name == PREFIX + name]


def _parents(run, name):
    return {s.parent.name[len(PREFIX):] for s in _named(run, name)}


# ---------------------------------------------------------------------------
# the spans
# ---------------------------------------------------------------------------
def test_plain_path_spans_nest_as_documented(session):
    run = session["plain"]
    names = {s.name[len(PREFIX):] for s in run["spans"]}
    assert names == {"pump", "admit", "dispatch_prefill", "dispatch_decode",
                     "harvest", "commit", "first_token", "retire"}
    assert all(s.parent.name == "tlm.test.plain" for s in _named(run, "pump"))
    for child in ("admit", "dispatch_decode", "harvest", "commit", "retire"):
        assert _parents(run, child) == {"pump"}, child
    # the monolithic prefill is dispatched while admitting
    assert _parents(run, "dispatch_prefill") == {"admit"}
    assert _parents(run, "first_token") == {"commit"}


def test_plain_path_span_counts_are_the_scheduler_s_own(session):
    run = session["plain"]
    b, done = run["batcher"], run["completions"]
    pumps = _named(run, "pump")
    assert [p.stats["turn"] for p in pumps] == list(range(b.turns))
    assert pumps[0].stats["queued"] == 3 and pumps[0].stats["live_slots"] == 0
    assert len(_named(run, "dispatch_decode")) == b.steps
    assert [d.stats["step"] for d in _named(run, "dispatch_decode")] == \
        list(range(b.steps))
    assert len(_named(run, "harvest")) == b.windows
    assert sum(h.stats["steps"] for h in _named(run, "harvest")) == b.steps
    assert sum(h.stats["firsts"] for h in _named(run, "harvest")) == 3
    assert sum(a.stats["admitted"] for a in _named(run, "admit")) == 3
    assert sum(r.stats["retired"] for r in _named(run, "retire")) == 3
    assert sum(c.stats["tokens"] for c in _named(run, "commit")) == \
        sum(len(c.tokens) for c in done.values()) == 3 * NEW


def test_each_request_has_one_prefill_dispatch_and_one_first_token(
        session, stack):
    run = session["plain"]
    prefills = _named(run, "dispatch_prefill")
    assert sorted(p.stats["uid"] for p in prefills) == ["r0", "r1", "r2"]
    assert sorted(f.stats["uid"] for f in _named(run, "first_token")) == \
        ["r0", "r1", "r2"]
    for p in prefills:
        i = int(p.stats["uid"][1:])
        assert p.stats["prompt_tokens"] == len(stack["prompts"][i])
        assert p.stats["chunk"] == -1 and p.stats["slot"] in (0, 1)
        (first,) = [f for f in _named(run, "first_token")
                    if f.stats["uid"] == p.stats["uid"]]
        assert first.start >= p.end and first.stats["slot"] == p.stats["slot"]


def test_two_slots_backpressure_the_third_request(session):
    """Two slots, three requests: the first turn admits two; the third
    waits for a slot (not for pages: ``backpressured`` stays 0)."""
    admits = _named(session["plain"], "admit")
    assert admits[0].stats["admitted"] == 2
    assert all(a.stats["backpressured"] == 0 for a in admits)
    assert _named(session["plain"], "pump")[1].stats["queued"] == 1


def test_chunked_path_dispatches_one_span_per_chunk(session, stack):
    run = session["chunked"]
    b = run["batcher"]
    # chunks are dispatched from the window, not while admitting
    assert _parents(run, "dispatch_prefill") == {"pump"}
    prefills = _named(run, "dispatch_prefill")
    assert len(prefills) == b.prefill_chunks
    for i, prompt in enumerate(stack["prompts"]):
        chunks = [p.stats["chunk"] for p in prefills
                  if p.stats["uid"] == f"r{i}"]
        assert chunks == list(range(-(-len(prompt) // CHUNK)))
    assert len(_named(run, "dispatch_decode")) == b.steps
    assert sorted(f.stats["uid"] for f in _named(run, "first_token")) == \
        ["r0", "r1", "r2"]
    assert [p.stats["turn"] for p in _named(run, "pump")] == \
        list(range(b.turns))


def test_speculative_path_drafts_before_every_verify_step(session):
    run = session["speculative"]
    b, done = run["batcher"], run["completions"]
    assert b.spec_stats["steps"] == b.steps > 0
    assert len(_named(run, "draft")) == len(_named(run, "dispatch_decode")) \
        == b.steps
    assert _parents(run, "draft") == {"pump"}
    assert all(d.stats["slots"] in (1, 2) for d in _named(run, "draft"))
    # every verify step resolves on the spot: one harvest with steps=1
    assert sum(h.stats["steps"] for h in _named(run, "harvest")) == b.steps
    assert sum(c.stats["tokens"] for c in _named(run, "commit")) == \
        sum(len(c.tokens) for c in done.values()) == 3 * NEW
    assert sorted(f.stats["uid"] for f in _named(run, "first_token")) == \
        ["r0", "r1", "r2"]


@pytest.mark.parametrize("path", ["plain", "chunked", "speculative"])
def test_a_turn_s_children_and_self_time_make_up_its_duration(session, path):
    """Spans of a turn are disjoint and inside it, so the reader's
    split (self times sum to the turn) holds on a real trace."""
    run = session[path]
    for pump in _named(run, "pump"):
        kids = sorted((s for s in run["spans"] if s.parent is pump),
                      key=lambda s: s.start)
        assert kids and kids[0].start >= pump.start
        assert kids[-1].end <= pump.end
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))


def test_no_session_records_nothing_and_serves_the_same_tokens(session):
    """The batcher that ran before the session left no span behind: the
    trace holds exactly the traced batchers' turns."""
    traced = ("plain", "chunked", "speculative")
    assert session["untraced"]["batcher"].turns > 0
    assert len([s for s in session["all"] if s.name == PREFIX + "pump"]) == \
        sum(session[p]["batcher"].turns for p in traced)
    want = {u: c.tokens for u, c in
            session["untraced"]["completions"].items()}
    for path in traced:
        got = {u: c.tokens for u, c in session[path]["completions"].items()}
        assert got == want, path


def test_arrival_time_becomes_queue_wait(session):
    """The traced plain run stamped ``arrival_s`` 0.25 s before it
    began; the others carried none."""
    run = session["plain"]
    for uid, c in run["completions"].items():
        assert 0.25 <= c.queue_wait_s < 60.0
        assert c.ttft_s > 0                     # still from admission
        (p,) = [p for p in _named(run, "dispatch_prefill")
                if p.stats["uid"] == uid]
        assert p.stats["queue_wait_us"] == int(1e6 * c.queue_wait_s)
    # the third request waited a whole window longer than the first two
    waits = [run["completions"][f"r{i}"].queue_wait_s for i in range(3)]
    assert waits[2] > max(waits[:2])
    for path in ("untraced", "chunked"):
        assert all(c.queue_wait_s is None
                   for c in session[path]["completions"].values())
    assert all("queue_wait_us" not in p.stats
               for p in _named(session["chunked"], "dispatch_prefill"))


def test_host_span_adds_late_stats_and_costs_nothing_without_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with host_span("test.free", a=1) as span:
        span.set_metadata(b=2)                  # no session: a no-op
    t0 = time.perf_counter()
    for i in range(2000):
        with host_span("test.free", a=i, b=2):
            pass
    assert (time.perf_counter() - t0) / 2000 < 50e-6


# ---------------------------------------------------------------------------
# phases inside the compiled serving programs
# ---------------------------------------------------------------------------
def _serving_text(stack, which: str) -> str:
    fns, ccfg, params = stack["fns"], stack["ccfg"], stack["params"]
    pools = init_pools(ccfg)
    carry = init_carry(SLOTS, sharding=fns.decode.carry_sharding)
    table = jnp.zeros((SLOTS, ccfg.pages_per_seq), jnp.int32)
    key, i32 = jax.random.PRNGKey(0), jnp.int32
    lowered = {
        "prefill": lambda: fns.prefill_jit.lower(
            params, pools, jnp.zeros((1, MAXP), i32), i32(3), table[0], key),
        "chunk": lambda: fns.chunk_jit.lower(
            params, pools, jnp.zeros((1, CHUNK), i32), i32(0), i32(3),
            i32(0), table[0], key),
        "decode": lambda: fns.decode_jit.lower(params, pools, carry, table),
        "spec": lambda: fns.spec_jit.lower(
            params, pools, carry, table, jnp.zeros((SLOTS, K), i32),
            jnp.zeros((SLOTS,), i32)),
    }[which]()
    return lowered.compile().as_text()


@pytest.mark.parametrize("which,scope", [
    ("prefill", "tlm.prefill"), ("chunk", "tlm.prefill"),
    ("decode", "tlm.decode"), ("spec", "tlm.decode")])
def test_compiled_serving_program_holds_its_phase(stack, which, scope):
    text = _serving_text(stack, which)
    # the program keeps the name the benchmark's readers find it by
    assert text.startswith(f"HloModule jit__{which},")
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert any(f"jit(_{which})/{scope}/" in n for n in op_names)
    other = {"tlm.prefill": "tlm.decode", "tlm.decode": "tlm.prefill"}[scope]
    assert not any(other in n for n in op_names)


# ---------------------------------------------------------------------------
# kernels found by name
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("remat,policy,recomputed", [
    (True, None, False), (True, "nothing_saveable", True),
    (False, None, False)], ids=["True", "True-nothing_saveable", "False"])
def test_train_step_s_compiled_text_names_its_attention_kernels(
        stack, remat, policy, recomputed):
    """The kernel path is forced (``attention_impl="mid"``: off the TPU
    ``auto`` resolves to XLA, and the forced kernel runs in interpret
    mode).  Forward and backward carry their own names under the layer
    scan's ``while``.  Under the default remat policy the forward's
    ``out`` and ``lse`` are kept (PR 27), so it is NOT there a second
    time; under ``nothing_saveable`` it is, under
    ``rematted_computation``."""
    from apex_tpu.models import GPTConfig, GPTModel

    override = {} if policy is None else {"remat_policy": policy}
    model = GPTModel(GPTConfig(
        vocab_size=64, num_layers=2, hidden_size=32, num_attention_heads=4,
        max_position_embeddings=16, compute_dtype=jnp.float32, remat=remat,
        attention_impl="mid", **override))
    params = model.init(jax.random.PRNGKey(0))
    specs = model.param_specs()
    step = jax.jit(jax.shard_map(
        lambda p, t, y: jax.value_and_grad(model.loss)(p, t, y),
        mesh=stack["mesh"], in_specs=(specs, P("dp"), P("dp")),
        out_specs=(P(), specs)))
    tokens = jnp.zeros((1, 16), jnp.int32)
    text = step.lower(params, tokens, tokens).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    fwd, bwd = kernel_name("fmha_mid.fwd"), kernel_name("fmha_mid.bwd")
    assert fwd == "tlm.kernel.fmha_mid.fwd"
    plain_fwd = [n for n in op_names
                 if fwd in n and "rematted_computation" not in n]
    remat_fwd = [n for n in op_names
                 if fwd in n and "rematted_computation" in n]
    assert plain_fwd and all("while/body" in n for n in plain_fwd)
    assert any(bwd in n and "transpose(jvp" in n for n in op_names)
    assert bool(remat_fwd) == recomputed
    assert not any(bwd in n and fwd in n for n in op_names)


def test_every_mosaic_kernel_call_is_named():
    """Each ``pl.pallas_call`` under ``ops/`` passes
    ``name=kernel_name(...)``, and no two calls share a name."""
    names, calls = [], 0
    for path in glob.glob(os.path.join(REPO, "apex_tpu", "ops", "*.py")):
        with open(path) as f:
            text = f.read()
        calls += len(re.findall(r"^[^#\n]*pl\.pallas_call\(", text, re.M))
        names += re.findall(r'name=kernel_name\("([\w.]+)"\)', text)
    assert calls == len(names) == 17
    assert len(set(names)) == len(names)
    assert {"fmha_mid.fwd", "fmha_mid.bwd", "paged_decode", "latent_walk",
            "hc_map", "moe_grouped.gate_up", "moe_grouped.down",
            "ssm_state_update"} <= set(names)
    assert kernel_name("paged_decode") == KERNEL_PREFIX + "paged_decode"
