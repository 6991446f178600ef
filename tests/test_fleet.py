"""Fleet tier: SLO policy and admission control, prefix-affinity
routing, the replayable request log, zero-loss replica failover, the
deterministic load generator, and the metrics-report fleet section.

The load-bearing claims, each pinned here:

- :class:`FleetPolicy` is the one validated spec: bad routing modes,
  duplicate classes and unknown class lookups fail loudly at
  construction, not mid-trace;
- admission control rejects (never hangs, never loses) requests that
  can never be served — replay headroom included — and classes at
  ``max_queue``;
- the routing key (:func:`prompt_page_hashes`) is replica-independent
  and affinity routing sends shared-prefix cohorts to the replica
  holding their pages;
- :class:`RequestLog` + :func:`resume_request` reconstruct a migrated
  request as prompt + committed tokens with the budget shrunk, and a
  killed replica's in-flight work completes elsewhere token-identical
  to an unkilled run;
- the SAME ``Request.seed`` produces the SAME sampled stream across
  DIFFERENT batcher instances, admission orders and slot assignments
  (the cross-replica determinism the failover contract stands on);
- ``tools/load_gen.py`` traces are byte-deterministic per seed, and a
  replay's records score into the fleet section of
  ``tools/metrics_report.py``;
- ``bench.py`` extras MERGE into BENCH_EXTRA.json — a fleet-only run
  must not clobber rows an earlier fuller capture wrote.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_tpu.fleet import (
    BATCH,
    INTERACTIVE,
    FleetPolicy,
    FleetRouter,
    LogEntry,
    Replica,
    RequestLog,
    SLOClass,
    resume_request,
)
from apex_tpu.serving.kv_cache import (
    KVCacheConfig,
    PagedKVCache,
    init_pools,
    prompt_page_hashes,
)
from apex_tpu.serving.serve import ContinuousBatcher, Request


# ---------------------------------------------------------------------------
# policy + request log: pure host, no model
# ---------------------------------------------------------------------------


class TestPolicy:
    def test_defaults(self):
        p = FleetPolicy()
        assert p.routing == "affinity"
        assert p.classes == (INTERACTIVE, BATCH)
        assert p.cls("interactive").priority < p.cls("batch").priority

    def test_bad_routing_rejected(self):
        with pytest.raises(ValueError, match="routing"):
            FleetPolicy(routing="hash_ring")

    def test_duplicate_classes_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            FleetPolicy(classes=(INTERACTIVE, SLOClass("interactive")))

    def test_unknown_class_lookup_rejected(self):
        with pytest.raises(ValueError, match="unknown SLO class"):
            FleetPolicy().cls("premium")

    def test_slo_class_validation(self):
        with pytest.raises(ValueError, match="name"):
            SLOClass("")
        with pytest.raises(ValueError, match="max_queue"):
            SLOClass("x", max_queue=0)


class TestRequestLog:
    def _entry(self, log, uid="a", plen=6, new=8, seed=7):
        return log.admit(
            Request(uid=uid, prompt=list(range(1, plen + 1)),
                    max_new_tokens=new, seed=seed),
            slo="interactive", replica="r0", t_arrive=10.0)

    def test_duplicate_uid_rejected(self):
        log = RequestLog()
        self._entry(log)
        with pytest.raises(ValueError, match="already logged"):
            self._entry(log)

    def test_progress_only_from_current_holder(self):
        log = RequestLog()
        e = self._entry(log)
        log.record_progress("r1", {"a": [5, 6]}, now=11.0)
        assert e.emitted == [] and e.t_first is None  # r1 doesn't hold it
        log.record_progress("r0", {"a": [5, 6]}, now=12.0)
        assert e.emitted == [5, 6]
        assert e.t_first == 12.0          # stamped at first non-empty
        log.record_progress("r0", {"a": [5, 6, 7]}, now=13.0)
        assert e.t_first == 12.0          # and never re-stamped

    def test_reassign_commits_emitted_as_replayed(self):
        log = RequestLog()
        e = self._entry(log)
        log.record_progress("r0", {"a": [5, 6]}, now=11.0)
        log.reassign("a", "r1")
        assert e.replica == "r1" and e.replays == 1
        assert e.replayed == [5, 6]
        # the new holder's own progress stacks on top of the replayed
        log.record_progress("r1", {"a": [7]}, now=12.0)
        assert e.emitted == [5, 6, 7]

    def test_resume_request_replays_suffix_and_shrinks_budget(self):
        log = RequestLog()
        e = self._entry(log, plen=4, new=8)
        log.record_progress("r0", {"a": [9, 9, 8]}, now=11.0)
        r = resume_request(e)
        assert r.uid == "a" and r.seed == 7
        assert r.prompt == [1, 2, 3, 4, 9, 9, 8]
        assert r.max_new_tokens == 5
        # the ORIGINAL request is never mutated
        assert list(e.request.prompt) == [1, 2, 3, 4]

    def test_resume_with_spent_budget_rejected(self):
        log = RequestLog()
        e = self._entry(log, new=2)
        log.record_progress("r0", {"a": [3, 4]}, now=11.0)
        with pytest.raises(ValueError, match="no budget"):
            resume_request(e)

    def test_inflight_on_excludes_done_and_other_replicas(self):
        log = RequestLog()
        self._entry(log, uid="a")
        self._entry(log, uid="b")
        log.reassign("b", "r1")
        log.complete("a", [1], "budget", now=11.0)
        assert log.inflight_on("r0") == []
        assert [e.request.uid for e in log.inflight_on("r1")] == ["b"]
        assert log.pending() == 1


class TestRoutingKey:
    def test_prompt_page_hashes_only_full_pages(self):
        p = list(range(1, 11))
        assert len(prompt_page_hashes(p, 4)) == 2     # 10 toks -> 2 pages
        assert prompt_page_hashes(p[:3], 4) == []     # sub-page: no key

    def test_hashes_are_cumulative(self):
        a = prompt_page_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
        b = prompt_page_hashes([9, 9, 9, 9, 5, 6, 7, 8], 4)
        assert a[0] != b[0]
        assert a[1] != b[1]   # same page content, different prefix

    def test_match_len_probe_is_read_only(self):
        cfg = KVCacheConfig(num_layers=1, num_heads=1, head_dim=4,
                            num_pages=16, page_size=4, max_seqs=2,
                            pages_per_seq=4)
        cache = PagedKVCache(cfg)
        hashes = prompt_page_hashes(list(range(1, 9)), 4)
        free0 = cache.allocator.num_free
        assert cache.match_len(hashes) == 0           # cold cache
        assert cache.allocator.num_free == free0      # no allocation


# ---------------------------------------------------------------------------
# router over the tiny GPT
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet_setup():
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    model = GPTModel(GPTConfig(
        vocab_size=64, num_layers=2, hidden_size=32,
        num_attention_heads=4, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat=False, attention_impl="xla",
    ))
    params = model.init(jax.random.PRNGKey(5))
    page, new, maxp = 4, 6, 24
    pps = -(-(maxp + new) // page)
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=4, head_dim=8,
        num_pages=1 + 4 * pps, page_size=page, max_seqs=2,
        pages_per_seq=pps, dtype=jnp.float32)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=maxp,
                           prefill_chunk=4)
    yield mesh, model, params, ccfg, fns, maxp
    parallel_state.destroy_model_parallel()


def _replicas(ccfg, fns, maxp, n=2):
    return [
        Replica(f"r{i}", ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(ccfg),
            init_pools(ccfg), max_prompt_len=maxp, harvest_every=2,
            chunk_fn=fns.chunk, prefill_chunk=4, prefix_cache=True))
        for i in range(n)
    ]


def _req(uid, prompt, new=4, seed=None):
    return Request(uid=uid, prompt=prompt, max_new_tokens=new,
                   seed=seed)


class TestFleetRouter:
    def test_replicas_must_share_page_size(self, fleet_setup):
        mesh, model, params, ccfg, fns, maxp = fleet_setup
        other = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8, num_pages=17,
            page_size=8, max_seqs=2, pages_per_seq=4)
        reps = _replicas(ccfg, fns, maxp, n=1) + [
            Replica("odd", ContinuousBatcher(
                fns.prefill, fns.decode, PagedKVCache(other),
                init_pools(other), max_prompt_len=maxp))]
        with pytest.raises(ValueError, match="page_size"):
            FleetRouter(reps)

    def test_admission_rejects_unservable_and_full_queues(
            self, fleet_setup):
        mesh, model, params, ccfg, fns, maxp = fleet_setup
        policy = FleetPolicy(classes=(
            SLOClass("interactive", 0, max_queue=1),
            SLOClass("batch", 1)))
        router = FleetRouter(_replicas(ccfg, fns, maxp), policy)
        # replay headroom: prompt + max_new - 1 must fit max_prompt_len
        assert not router.submit(_req("big", [1] * 20, new=10))
        assert router.rejected["big"] == "too_large"
        assert router.submit(_req("a", [1, 2, 3], new=4))
        assert not router.submit(_req("b", [1, 2, 4], new=4))
        assert router.rejected["b"] == "queue_full"
        # a lower-priority class still has room
        assert router.submit(_req("c", [1, 2, 5], new=4), "batch")
        assert router.pending == 2
        router.drain()
        assert sorted(router.completions) == ["a", "c"]

    def test_affinity_routes_cohort_to_prefix_holder(self, fleet_setup):
        """After one cohort request lands on a replica, every later
        request sharing its page-aligned prefix follows it — and the
        router's second choice balances to the OTHER replica."""
        mesh, model, params, ccfg, fns, maxp = fleet_setup
        router = FleetRouter(_replicas(ccfg, fns, maxp))
        rng = np.random.RandomState(9)
        pref_a = [int(t) for t in rng.randint(1, 64, (8,))]
        pref_b = [int(t) for t in rng.randint(1, 64, (8,))]
        router.submit(_req("a0", pref_a + [1, 2]))
        router.drain()
        home = router.log.get("a0").replica
        other = ({"r0", "r1"} - {home}).pop()
        router.submit(_req("b0", pref_b + [3, 4]))   # cold: least-loaded
        router.drain()
        assert router.log.get("b0").replica == other
        for i, (tag, pref) in enumerate(
                [("a", pref_a), ("b", pref_b)] * 2):
            router.submit(_req(f"{tag}{i + 1}", pref + [9, i]))
        router.drain()
        for uid, e in router.log._entries.items():
            want = home if uid.startswith("a") else other
            assert e.replica == want, (uid, e.replica)
        assert router.stats["affinity_routed"] >= 4

    def test_round_robin_ignores_affinity_and_priority(
            self, fleet_setup):
        mesh, model, params, ccfg, fns, maxp = fleet_setup
        router = FleetRouter(_replicas(ccfg, fns, maxp),
                             FleetPolicy(routing="round_robin"))
        shared = [7] * 8
        for i in range(4):
            router.submit(_req(f"u{i}", shared + [i]))
        assert router.stats["routed"] == {"r0": 2, "r1": 2}
        assert router.stats["affinity_routed"] == 0
        router.drain()
        assert len(router.completions) == 4

    def test_pump_order_is_class_priority_then_fifo(self, fleet_setup):
        mesh, model, params, ccfg, fns, maxp = fleet_setup
        router = FleetRouter(_replicas(ccfg, fns, maxp, n=1))
        router.submit(_req("b1", [1, 2], new=2), "batch")
        router.submit(_req("i1", [1, 3], new=2), "interactive")
        router.submit(_req("b2", [1, 4], new=2), "batch")
        router.submit(_req("i2", [1, 5], new=2), "interactive")
        order = [r.uid for r in router._pump_order("r0")]
        assert order == ["i1", "i2", "b1", "b2"]
        router.drain()
        assert len(router.completions) == 4


class TestFleetFailover:
    def test_kill_drill_zero_lost_token_identical(self, fleet_setup):
        """r0 dies after 2 windows with work queued AND in flight: every
        request completes, >= 1 migrates, and every greedy stream is
        identical to an unkilled reference run."""
        mesh, model, params, ccfg, fns, maxp = fleet_setup
        rng = np.random.RandomState(17)
        reqs = [
            _req(f"u{i}", [int(t) for t in
                           rng.randint(1, 64, (6 + (i % 3) * 4,))],
                 new=6)
            for i in range(8)
        ]

        def run(fail):
            router = FleetRouter(_replicas(ccfg, fns, maxp))
            if fail:
                router.replicas[0].fail_after(2)
            for r in reqs:
                assert router.submit(r)
            router.drain()
            return router

        ref = run(fail=False)
        drill = run(fail=True)
        assert not drill.replicas[0].alive
        assert drill.stats["migrations"] >= 1
        assert len(drill.completions) == len(reqs)
        for uid, comp in ref.completions.items():
            assert drill.completions[uid].tokens == comp.tokens, uid
        migrated = [u for u, c in drill.completions.items()
                    if c.replays > 0]
        assert migrated, "nothing actually migrated mid-flight"

    def test_dead_fleet_raises_not_hangs(self, fleet_setup):
        mesh, model, params, ccfg, fns, maxp = fleet_setup
        router = FleetRouter(_replicas(ccfg, fns, maxp))
        router.submit(_req("a", [1, 2, 3]))
        for r in router.replicas:
            r.kill()
        with pytest.raises(RuntimeError, match="no replica is alive"):
            router.drain()

    def test_dead_fleet_names_the_pump_error(self, fleet_setup):
        """When the fleet died of its replicas' own faults, the error
        carries the last pump exception as its cause — a compile
        failure must not read as "dead fleet"."""
        mesh, model, params, ccfg, fns, maxp = fleet_setup
        router = FleetRouter(_replicas(ccfg, fns, maxp))

        def boom(work):
            raise ValueError("lowering failed")

        for r in router.replicas:
            r.batcher.pump = boom
        router.submit(_req("a", [1, 2, 3]))
        with pytest.raises(RuntimeError,
                           match="no replica is alive") as exc:
            router.drain()
        assert isinstance(exc.value.__cause__, ValueError)
        assert "lowering failed" in str(exc.value.__cause__)


class TestCrossReplicaSamplingDeterminism:
    def test_same_seed_same_stream_across_batchers_and_order(
            self, fleet_setup):
        """The failover contract's foundation: a seeded request's
        SAMPLED stream is identical across different batcher
        instances, admission orders and therefore slot assignments."""
        mesh, model, params, ccfg, fns_greedy, maxp = fleet_setup
        fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=maxp,
                               temperature=0.9, top_k=20,
                               prefill_chunk=4)
        rng = np.random.RandomState(23)
        reqs = [
            _req(f"s{i}",
                 [int(t) for t in rng.randint(1, 64, (5 + i,))],
                 new=6, seed=100 + i)
            for i in range(4)
        ]

        def serve(order):
            b = ContinuousBatcher(
                fns.prefill, fns.decode, PagedKVCache(ccfg),
                init_pools(ccfg), max_prompt_len=maxp,
                harvest_every=2, chunk_fn=fns.chunk, prefill_chunk=4,
                prefix_cache=True)
            comps = b.run([reqs[i] for i in order])
            return {u: c.tokens for u, c in comps.items()}

        first = serve([0, 1, 2, 3])
        assert any(len(set(t)) > 1 for t in first.values())
        assert serve([3, 2, 1, 0]) == first
        assert serve([2, 0, 3, 1]) == first


# ---------------------------------------------------------------------------
# load generator + metrics report + bench merge
# ---------------------------------------------------------------------------


class TestLoadGen:
    def test_trace_is_deterministic_per_seed(self):
        from tools.load_gen import make_trace

        kw = dict(n_requests=12, seed=4, vocab_size=64)
        a, b = make_trace(**kw), make_trace(**kw)
        assert [(x.t, x.slo, x.cohort, x.request.prompt,
                 x.request.max_new_tokens, x.request.seed)
                for x in a] == \
               [(x.t, x.slo, x.cohort, x.request.prompt,
                 x.request.max_new_tokens, x.request.seed)
                for x in b]
        c = make_trace(**{**kw, "seed": 5})
        assert [x.request.prompt for x in c] != \
               [x.request.prompt for x in a]

    def test_cohort_requests_share_the_prefix(self):
        from tools.load_gen import make_trace

        trace = make_trace(n_requests=32, seed=1, vocab_size=64,
                           cohorts=2, cohort_frac=1.0, prefix_len=8,
                           prompt_len=(9, 16))
        by_cohort = {}
        for it in trace:
            by_cohort.setdefault(it.cohort, set()).add(
                tuple(it.request.prompt[:8]))
        assert set(by_cohort) == {0, 1}
        assert all(len(v) == 1 for v in by_cohort.values())

    def test_validation(self):
        from tools.load_gen import make_trace

        with pytest.raises(ValueError, match="prefix_len"):
            make_trace(n_requests=1, seed=0, vocab_size=64,
                       prefix_len=48, prompt_len=(8, 48))
        with pytest.raises(ValueError, match="burstiness"):
            make_trace(n_requests=1, seed=0, vocab_size=64,
                       burstiness=0.5)

    def test_summarize_trace_ledger(self):
        from tools.load_gen import summarize_trace

        records = [
            {"uid": "a", "slo": "interactive", "reason": "budget",
             "ttft_s": 0.1, "itl_ms": 2.0, "replays": 1},
            {"uid": "b", "slo": "batch", "reason": "budget",
             "ttft_s": 0.4, "itl_ms": 3.0},
            {"uid": "c", "slo": "interactive", "rejected": "too_large"},
            {"uid": "d", "slo": "batch", "lost": True},
        ]
        s = summarize_trace(records)
        assert (s["requests"], s["completed"], s["rejected"],
                s["lost"], s["migrated"]) == (4, 2, 1, 1, 1)
        assert s["by_class"]["interactive"]["ttft_s"]["p50"] == 0.1
        assert s["overall"]["itl_ms"]["p99"] == 3.0

    @pytest.mark.slow
    def test_replay_end_to_end_scores_in_metrics_report(
            self, fleet_setup, tmp_path):
        """Trace replay through a logged 2-replica fleet: every request
        completes, the replay records summarize, and the jsonl stream
        renders a fleet section plus EXACT admit-to-first-token TTFTs
        in tools/metrics_report.py."""
        from apex_tpu.telemetry.metrics import MetricsLogger
        from tools.load_gen import make_trace, replay, summarize_trace
        import tools.metrics_report as mr

        mesh, model, params, ccfg, fns, maxp = fleet_setup
        jsonl = str(tmp_path / "fleet.jsonl")
        logger = MetricsLogger(jsonl_path=jsonl, console=False)
        reps = [
            Replica(f"r{i}", ContinuousBatcher(
                fns.prefill, fns.decode, PagedKVCache(ccfg),
                init_pools(ccfg), max_prompt_len=maxp,
                harvest_every=2, chunk_fn=fns.chunk, prefill_chunk=4,
                prefix_cache=True, logger=logger))
            for i in range(2)
        ]
        router = FleetRouter(reps, logger=logger)
        # prompt + budget - 1 must clear max_prompt_len=24 (replay
        # headroom), so cap prompts at 18 with a 6-token budget
        trace = make_trace(n_requests=12, seed=3, vocab_size=64,
                           prompt_len=(8, 18), new_tokens=(3, 6),
                           cohorts=2, prefix_len=7)
        recs = replay(router, trace)
        logger.close()
        s = summarize_trace(recs)
        assert s["completed"] == 12 and s["lost"] == 0
        summary = mr.summarize(mr.load_records(jsonl))
        assert summary["serving"]["ttft_s"]["source"] == "exact"
        fl = summary["fleet"]
        assert fl["trace"] == {"requests": 12, "completed": 12,
                               "lost": 0}
        assert sum(fl["routed"].values()) == 12
        text = mr.format_report(summary)
        assert "fleet summary:" in text
        assert "exact admit-to-first-token" in text


class TestBenchExtraMerge:
    def test_merge_preserves_existing_rows(self, tmp_path):
        import bench

        path = str(tmp_path / "BENCH_EXTRA.json")
        with open(path, "w") as f:
            json.dump({"decode": {"metric": "old"},
                       "platform": "tpu"}, f)
        bench._merge_bench_extra(
            path, {"fleet": {"metric": "fleet_x"}, "platform": "cpu"})
        with open(path) as f:
            merged = json.load(f)
        assert merged["decode"] == {"metric": "old"}   # not clobbered
        assert merged["fleet"] == {"metric": "fleet_x"}
        assert merged["platform"] == "cpu"             # fresh key wins

    def test_merge_survives_corrupt_or_missing_file(self, tmp_path):
        import bench

        path = str(tmp_path / "BENCH_EXTRA.json")
        bench._merge_bench_extra(path, {"fleet": 1})
        with open(path) as f:
            assert json.load(f) == {"fleet": 1}
        with open(path, "w") as f:
            f.write("{not json")
        bench._merge_bench_extra(path, {"fleet": 2})
        with open(path) as f:
            assert json.load(f) == {"fleet": 2}

    def test_fleet_child_is_dispatchable(self):
        """The orchestrator's --child fleet row must resolve to the
        child function (a typo'd dispatcher entry dies at gate time,
        not test time)."""
        import bench

        assert callable(bench.child_fleet)
        src = open(bench.__file__).read()
        assert 'kind == "fleet"' in src
