"""Serving fault-tolerance tier: the durable request journal, replica
health monitoring, deadlines + hedged re-routing, brownout degradation
and the serving fault seams.

The load-bearing claims, each pinned here:

- :class:`RequestJournal` is write-ahead (an admission is on disk
  before serving starts), CRC-checked per record, and atomic-append —
  :func:`recover_journal` survives torn tails, flipped bits and lost
  delta records (a gap FREEZES the stream at the consistent prefix,
  it never stitches across a hole);
- a full restart — new batchers, new router, journal replayed —
  resumes every in-flight request token-identically and keeps every
  completed stream, with zero new jit cache entries;
- a pump that raises is a counted replica fault; enough consecutive
  faults (or one stalled pump past ``pump_timeout_s``) quarantine the
  replica and its work migrates with zero losses, token-identically;
  a single transient fault does NOT quarantine;
- impossible deadlines are rejected at admission with the distinct
  ``deadline_unmeetable`` reason; a missed deadline retries (re-armed,
  token-identical) or terminates with a stream that is a committed
  PREFIX of the reference — never garbage;
- a hedged duplicate resolves first-commit-wins with the stream
  token-identical either way, and loses cleanly when the primary
  lands first;
- the brownout ladder escalates under queue pressure (speculation
  off -> chunk throttle -> shed the batch class), de-escalates with
  hysteresis, and never changes a token — the levers are scheduling
  only;
- ``ContinuousBatcher.cancel()`` is safe mid-speculation-window:
  survivors' streams are untouched, pages are released, the slot is
  reusable (the regression test speculation's cancel path rides on);
- every pump heartbeat carries the replica's name so
  ``resilience.watchdog.read_heartbeat`` can name a stalled replica.
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
    BrownoutPolicy,
    FleetPolicy,
    FleetRouter,
    Replica,
    RequestJournal,
    RequestLog,
    SLOClass,
    recover_journal,
)
from apex_tpu.resilience import faults
from apex_tpu.serving.kv_cache import (
    KVCacheConfig,
    PagedKVCache,
    init_pools,
)
from apex_tpu.serving.serve import ContinuousBatcher, Request


# ---------------------------------------------------------------------------
# journal: pure host, no model
# ---------------------------------------------------------------------------


def _admit(log, journal, uid, *, plen=4, new=6, seed=7, slo="interactive",
           deadline=None):
    e = log.admit(Request(uid=uid, prompt=list(range(1, plen + 1)),
                          max_new_tokens=new, seed=seed),
                  slo=slo, replica="r0", t_arrive=1.0)
    if deadline is not None:
        e.deadline_rel = deadline
    journal.admit(e)
    return e


class TestJournal:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        log, j = RequestLog(), RequestJournal(path)
        _admit(log, j, "a", deadline=2.5)
        _admit(log, j, "b", seed=None, slo="batch")
        log.record_progress("r0", {"a": [5, 6]}, now=2.0)
        j.sync(log)
        log.record_progress("r0", {"a": [5, 6, 7]}, now=3.0)
        log.complete("b", [9], "eos", now=3.0)
        j.sync(log)
        j.close()
        rec = recover_journal(path)
        assert rec.corrupt == 0 and rec.gapped == 0
        a, b = rec.entries["a"], rec.entries["b"]
        assert a["request"].prompt == [1, 2, 3, 4]
        assert a["request"].max_new_tokens == 6
        assert a["request"].seed == 7
        assert a["slo"] == "interactive" and a["deadline_s"] == 2.5
        assert a["emitted"] == [5, 6, 7] and not a["done"]
        assert b["request"].seed is None
        assert b["done"] and b["reason"] == "eos" and b["emitted"] == [9]
        assert list(rec.inflight) == ["a"]
        assert list(rec.completed) == ["b"]

    def test_write_ahead_admit_lands_immediately(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        log, j = RequestLog(), RequestJournal(path)
        _admit(log, j, "a")
        # no sync, no close: the admit must already be durable
        rec = recover_journal(path)
        assert list(rec.entries) == ["a"]

    def test_sync_batches_one_append_per_step(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        log, j = RequestJournal(path), None
        log, j = RequestLog(), RequestJournal(path)
        for uid in ("a", "b", "c"):
            _admit(log, j, uid)
        appends0 = j.stats["appends"]
        log.record_progress("r0", {"a": [1], "b": [2], "c": [3]}, now=2.0)
        j.sync(log)
        assert j.stats["appends"] == appends0 + 1   # 3 deltas, ONE write
        assert j.stats["records"] >= 6
        assert j.stats["write_s"] >= 0.0

    def test_crc_flip_detected(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        log, j = RequestLog(), RequestJournal(path)
        _admit(log, j, "a")
        _admit(log, j, "b")
        j.close()
        lines = open(path, "rb").read().splitlines(keepends=True)
        # tamper a payload byte of the FIRST record, CRC untouched
        tampered = lines[0].replace(b'"budget":6', b'"budget":7')
        assert tampered != lines[0]
        with open(path, "wb") as f:
            f.writelines([tampered] + lines[1:])
        rec = recover_journal(path)
        assert rec.corrupt == 1
        assert list(rec.entries) == ["b"]          # the clean record

    def test_torn_tail_skipped(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        log, j = RequestLog(), RequestJournal(path)
        _admit(log, j, "a")
        log.record_progress("r0", {"a": [5]}, now=2.0)
        j.sync(log)
        j.close()
        size = os.path.getsize(path)
        os.truncate(path, size - 7)                # tear the last line
        rec = recover_journal(path)
        assert rec.corrupt == 1
        assert rec.entries["a"]["emitted"] == []   # frozen pre-tear

    def test_gap_freezes_at_consistent_prefix(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        log, j = RequestLog(), RequestJournal(path)
        _admit(log, j, "a")
        log.record_progress("r0", {"a": [5, 6]}, now=2.0)
        j.sync(log)
        log.record_progress("r0", {"a": [5, 6, 7, 8]}, now=3.0)
        j.sync(log)
        j.close()
        lines = open(path, "rb").read().splitlines(keepends=True)
        assert len(lines) == 3
        with open(path, "wb") as f:                # drop the 1st delta
            f.writelines([lines[0], lines[2]])
        rec = recover_journal(path)
        assert rec.gapped == 1
        # off=2 disagrees with the empty accumulated stream: frozen at
        # the admit-level prefix, NOT stitched as [7, 8]
        assert rec.entries["a"]["emitted"] == []
        assert not rec.entries["a"]["done"]

    def test_unjournalable_uid_rejected(self, tmp_path):
        log = RequestLog()
        j = RequestJournal(str(tmp_path / "j.jsonl"))
        e = log.admit(Request(uid=("t", 1), prompt=[1, 2],
                              max_new_tokens=2, seed=1),
                      slo="interactive", replica="r0", t_arrive=0.0)
        with pytest.raises(ValueError, match="uids must be str or int"):
            j.admit(e)

    def test_missing_file_recovers_empty(self, tmp_path):
        rec = recover_journal(str(tmp_path / "nope.jsonl"))
        assert rec.entries == {} and rec.records == 0

    def test_prime_appends_only_new_tokens(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        log, j = RequestLog(), RequestJournal(path)
        _admit(log, j, "a")
        log.record_progress("r0", {"a": [5, 6]}, now=2.0)
        j.sync(log)
        j.close()
        # "restart": a fresh journal on the SAME path, cursor primed
        log2 = RequestLog()
        e2 = log2.admit(Request(uid="a", prompt=[1, 2, 3, 4],
                                max_new_tokens=6, seed=7),
                        slo="interactive", replica="r0", t_arrive=9.0)
        e2.emitted = [5, 6]
        j2 = RequestJournal(path)
        j2.prime(log2)
        log2.record_progress("r0", {"a": [5, 6, 7]}, now=10.0)
        j2.sync(log2)
        j2.close()
        rec = recover_journal(path)
        assert rec.corrupt == 0 and rec.gapped == 0
        assert rec.entries["a"]["emitted"] == [5, 6, 7]


# ---------------------------------------------------------------------------
# policy validation: pure host
# ---------------------------------------------------------------------------


class TestFaultPolicyValidation:
    def test_slo_deadline_fields(self):
        with pytest.raises(ValueError, match="deadline_s"):
            SLOClass("x", deadline_s=0)
        with pytest.raises(ValueError, match="max_retries"):
            SLOClass("x", max_retries=-1)
        with pytest.raises(ValueError, match="hedge_after_s"):
            SLOClass("x", hedge_after_s=0)

    def test_fleet_policy_fields(self):
        with pytest.raises(ValueError, match="step_floor_s"):
            FleetPolicy(step_floor_s=-1)
        with pytest.raises(ValueError, match="pump_timeout_s"):
            FleetPolicy(pump_timeout_s=0)
        with pytest.raises(ValueError, match="max_replica_faults"):
            FleetPolicy(max_replica_faults=0)

    def test_brownout_ladder_shape(self):
        BrownoutPolicy()                            # defaults are valid
        with pytest.raises(ValueError, match="3 rungs"):
            BrownoutPolicy(page_frac=(0.3, 0.1))
        with pytest.raises(ValueError, match="non-increasing"):
            BrownoutPolicy(page_frac=(0.1, 0.2, 0.05))
        with pytest.raises(ValueError, match="non-decreasing"):
            BrownoutPolicy(queue_depth=(8, 4, 16))
        with pytest.raises(ValueError, match="chunk_throttle"):
            BrownoutPolicy(chunk_throttle=1)
        with pytest.raises(ValueError, match="recover_margin"):
            BrownoutPolicy(recover_margin=1.0)


# ---------------------------------------------------------------------------
# the tiny-GPT fleet under injected faults
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def chaos_setup():
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


def _some_reqs(n=6, new=5, seed0=None, rng_seed=31):
    rng = np.random.RandomState(rng_seed)
    return [
        _req(f"u{i}",
             [int(t) for t in rng.randint(1, 64, (5 + (i % 3) * 3,))],
             new=new, seed=None if seed0 is None else seed0 + i)
        for i in range(n)
    ]


def _reference(ccfg, fns, maxp, reqs):
    router = FleetRouter(_replicas(ccfg, fns, maxp))
    for r in reqs:
        assert router.submit(r)
    router.drain()
    return {u: c.tokens for u, c in router.completions.items()}


class TestHealthMonitoring:
    def test_repeated_faults_quarantine_and_migrate(self, chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        reqs = _some_reqs()
        ref = _reference(ccfg, fns, maxp, reqs)
        router = FleetRouter(
            _replicas(ccfg, fns, maxp),
            FleetPolicy(max_replica_faults=2))
        for r in reqs:
            assert router.submit(r)
        r0 = router.replicas[0]
        with faults.nonfinite_logits(r0.batcher, nth=2, forever=True):
            router.drain()
        assert r0.quarantined == "faults"
        assert not r0.alive
        assert r0.consecutive_faults >= 2
        assert "FloatingPointError" in r0.last_error
        assert router.stats["quarantined"] == 1
        assert router.stats["replica_faults"] >= 2
        assert len(router.completions) == len(reqs)   # zero lost
        for uid, toks in ref.items():
            assert router.completions[uid].tokens == toks, uid

    def test_single_transient_fault_heals(self, chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        reqs = _some_reqs()
        ref = _reference(ccfg, fns, maxp, reqs)
        router = FleetRouter(
            _replicas(ccfg, fns, maxp),
            FleetPolicy(max_replica_faults=3))
        for r in reqs:
            assert router.submit(r)
        r0 = router.replicas[0]
        with faults.failing_windows(r0.batcher, nth=1, count=1):
            router.drain()
        assert r0.alive and r0.quarantined is None
        assert r0.faults == 1
        assert r0.consecutive_faults == 0       # reset by the recovery
        assert router.stats["quarantined"] == 0
        for uid, toks in ref.items():
            assert router.completions[uid].tokens == toks, uid

    def test_stalled_pump_quarantined(self, chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        reqs = _some_reqs()
        ref = _reference(ccfg, fns, maxp, reqs)
        router = FleetRouter(
            _replicas(ccfg, fns, maxp),
            FleetPolicy(pump_timeout_s=0.05))
        for r in reqs:
            assert router.submit(r)
        r0 = router.replicas[0]
        with faults.stalled_pump(r0.batcher, stall_s=0.2):
            router.drain()
        assert r0.quarantined == "stall"
        assert len(router.completions) == len(reqs)
        for uid, toks in ref.items():
            assert router.completions[uid].tokens == toks, uid

    def test_heartbeat_names_the_replica(self, chaos_setup, tmp_path,
                                         monkeypatch):
        from apex_tpu.resilience.watchdog import Watchdog, read_heartbeat

        mesh, model, params, ccfg, fns, maxp = chaos_setup
        hb = str(tmp_path / "heartbeat.json")
        wd = Watchdog(deadline_s=600, heartbeat_file=hb)
        router = FleetRouter(_replicas(ccfg, fns, maxp, n=1),
                             watchdog=wd)
        router.submit(_req("a", [1, 2, 3], new=3))
        wd._last_hb_write = 0.0                 # defeat the throttle
        router.step()
        rec = json.load(open(hb))
        assert rec["replica"] == "r0"
        assert "serving_step" in rec and "live_slots" in rec
        # the outside observer's view: the reader finds the file
        # through the environment and names the replica
        monkeypatch.setenv("APEX_TPU_HEARTBEAT_FILE", hb)
        seen = read_heartbeat()
        assert seen["replica"] == "r0" and "live_slots" in seen
        assert seen["age_s"] >= 0.0
        router.drain()


class TestDeadlines:
    def test_unmeetable_deadline_rejected_at_admission(self,
                                                       chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        policy = FleetPolicy(
            classes=(SLOClass("interactive", 0, deadline_s=30.0),
                     SLOClass("batch", 1)),
            step_floor_s=1.0)
        router = FleetRouter(_replicas(ccfg, fns, maxp), policy)
        # 8-token prompt = 2 chunks; +6 tokens -> 7 steps >= 7s floor
        assert not router.submit(_req("tight", [1] * 8, new=6),
                                 deadline_s=3.0)
        assert router.rejected["tight"] == "deadline_unmeetable"
        # the same request with the class's 30 s deadline admits
        assert router.submit(_req("ok", [1] * 8, new=6))
        router.drain()
        assert "ok" in router.completions

    def test_miss_retries_token_identical(self, chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        # 6 requests onto 4 fleet slots: the overflow queues past its
        # deadline, so misses are guaranteed
        reqs = _some_reqs(n=6, new=6)
        ref = _reference(ccfg, fns, maxp, reqs)
        clk = [0.0]
        policy = FleetPolicy(classes=(
            SLOClass("interactive", 0, deadline_s=2.0, max_retries=50),
            SLOClass("batch", 1)))
        router = FleetRouter(_replicas(ccfg, fns, maxp), policy,
                             clock=lambda: clk[0])
        for r in reqs:
            assert router.submit(r)
        while router.pending:
            router.step()
            clk[0] += 1.0
            assert clk[0] < 300, "deadline retries livelocked"
        assert router.stats["deadline_misses"] >= 1
        assert router.stats["deadline_retries"] >= 1
        assert len(router.completions) == len(reqs)
        for uid, toks in ref.items():
            c = router.completions[uid]
            assert c.reason != "deadline"
            assert c.tokens == toks, uid

    def test_miss_without_retries_terminates_with_prefix(self,
                                                         chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        reqs = _some_reqs(n=6, new=6)
        ref = _reference(ccfg, fns, maxp, reqs)
        clk = [0.0]
        policy = FleetPolicy(classes=(
            SLOClass("interactive", 0, deadline_s=3.0, max_retries=0),
            SLOClass("batch", 1)))
        router = FleetRouter(_replicas(ccfg, fns, maxp), policy,
                             clock=lambda: clk[0])
        for r in reqs:
            assert router.submit(r)
        while router.pending:
            router.step()
            clk[0] += 1.0
            assert clk[0] < 100
        dead = [u for u, c in router.completions.items()
                if c.reason == "deadline"]
        assert dead, "no deadline ever fired — the test proved nothing"
        assert router.stats["deadline_misses"] == len(dead)
        for uid, c in router.completions.items():
            full = ref[uid]
            # terminal-deadline streams are COMMITTED PREFIXES of the
            # reference — cut off, never corrupted
            assert c.tokens == full[:len(c.tokens)], uid
            if c.reason != "deadline":
                assert c.tokens == full, uid


class TestHedging:
    def test_hedge_wins_when_primary_is_stuck(self, chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        reqs = _some_reqs(n=2, new=5, seed0=400)
        ref = _reference(ccfg, fns, maxp, reqs)
        clk = [0.0]
        policy = FleetPolicy(
            classes=(SLOClass("interactive", 0, hedge_after_s=3.0),
                     SLOClass("batch", 1)),
            max_replica_faults=10_000)      # fault forever, no quarantine
        router = FleetRouter(_replicas(ccfg, fns, maxp), policy,
                             clock=lambda: clk[0])
        for r in reqs:
            assert router.submit(r)
        r0 = router.replicas[0]
        # every window on r0 raises: its requests make no progress, so
        # after hedge_after_s each spawns a duplicate on r1 and the
        # duplicate commits first
        with faults.failing_windows(r0.batcher, nth=1, count=10_000):
            while router.pending:
                router.step()
                clk[0] += 1.0
                assert clk[0] < 200, "hedged fleet livelocked"
        stuck = [u for u in ref
                 if router.log.get(u).replica == "r1"
                 and router.completions[u].hedged]
        assert router.stats["hedge_wins"] >= 1
        assert stuck, "no hedge ever won"
        for uid, toks in ref.items():
            assert router.completions[uid].tokens == toks, uid

    def test_hedge_loses_cleanly_when_primary_lands(self, chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        reqs = _some_reqs(n=2, new=8, seed0=500)
        ref = _reference(ccfg, fns, maxp, reqs)
        clk = [0.0]
        policy = FleetPolicy(
            classes=(SLOClass("interactive", 0, hedge_after_s=1.0),
                     SLOClass("batch", 1)))
        router = FleetRouter(_replicas(ccfg, fns, maxp), policy,
                             clock=lambda: clk[0])
        for r in reqs:
            assert router.submit(r)
        while router.pending:
            router.step()
            clk[0] += 1.0
            assert clk[0] < 200
        assert router.stats["hedges"] >= 1
        assert router.stats["hedge_losses"] >= 1
        assert not router._hedges                # no hedge left live
        for uid, toks in ref.items():
            assert router.completions[uid].tokens == toks, uid
        # the losers' slots and pages were actually released — every
        # page is either free or held (refcount 1) by the prefix index
        for r in router.replicas:
            assert r.batcher.live_slots == 0
            cache = r.batcher.cache
            assert (cache.allocator.num_free + cache.prefix_index_size
                    == cache.config.num_pages - 1)


class TestBrownout:
    def test_ladder_up_down_sheds_batch_and_keeps_tokens(self,
                                                         chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        reqs = _some_reqs(n=6, new=4)
        ref = _reference(ccfg, fns, maxp, reqs)
        bp = BrownoutPolicy(page_frac=(0.0, 0.0, 0.0),
                            queue_depth=(2, 3, 4),
                            chunk_throttle=2, recover_margin=1.5)
        router = FleetRouter(_replicas(ccfg, fns, maxp, n=1),
                             FleetPolicy(brownout=bp))
        for r in reqs:
            assert router.submit(r)
        router.step()                           # qd=6 >= 4: level 3
        assert router.brownout_level == 3
        b = router.replicas[0].batcher
        assert b.speculation_enabled is False
        assert b.chunk_throttle == 2
        # level 3 sheds the LOWEST-priority class at admission
        assert not router.submit(_req("shed", [1, 2, 3], new=2),
                                 "batch")
        assert router.rejected["shed"] == "brownout"
        # interactive still admits under the same pressure
        assert router.submit(_req("keep", [1, 2, 4], new=2),
                             "interactive")
        router.drain()
        # pressure cleared: the ladder walked back down (hysteresis
        # releases one rung per step; the drain has plenty)
        assert router.brownout_level < 3
        assert b.speculation_enabled or router.brownout_level >= 1
        assert router.stats["brownout_transitions"] >= 2
        # the levers are scheduling-only: every admitted stream is
        # token-identical to the no-brownout reference
        for uid, toks in ref.items():
            assert router.completions[uid].tokens == toks, uid
        assert "keep" in router.completions

    def test_page_pressure_rung_via_exhaust_pool(self, chaos_setup):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        bp = BrownoutPolicy(page_frac=(0.9, 0.05, 0.01),
                            queue_depth=(10_000,) * 3)
        router = FleetRouter(_replicas(ccfg, fns, maxp, n=1),
                             FleetPolicy(brownout=bp))
        cache = router.replicas[0].batcher.cache
        with faults.exhaust_pool(cache, leave_free=1):
            router.step()
            assert router.brownout_level >= 1
        # pages returned; de-escalation needs the recover margin, one
        # rung per step
        for _ in range(4):
            router.step()
        assert router.brownout_level == 0


class TestJournalRestart:
    def test_restart_resumes_token_identical(self, chaos_setup,
                                             tmp_path):
        mesh, model, params, ccfg, fns, maxp = chaos_setup
        path = str(tmp_path / "journal.jsonl")
        # mixed greedy + seeded-looking uids; greedy fns so identity is
        # exact (seeded identity is pinned at the dryrun tier)
        reqs = _some_reqs(n=5, new=6)
        ref = _reference(ccfg, fns, maxp, reqs)
        router = FleetRouter(_replicas(ccfg, fns, maxp),
                             journal=RequestJournal(path))
        for r in reqs:
            assert router.submit(r)
        for _ in range(4):                      # serve PARTWAY, then die
            router.step()
        done_before = dict(router.completions)
        assert router.pending > 0, "nothing in flight at the kill point"
        # ---- the process is gone.  A new one recovers from disk:
        rec = recover_journal(path)
        assert rec.corrupt == 0
        router2 = FleetRouter(_replicas(ccfg, fns, maxp),
                              journal=RequestJournal(path))
        out = router2.resume_from_journal(rec)
        assert out["resumed"] + out["completed"] == len(reqs)
        assert out["resumed"] >= 1
        router2.drain()
        assert len(router2.completions) == len(reqs)     # zero lost
        for uid, toks in ref.items():
            assert router2.completions[uid].tokens == toks, uid
        # completed-before-death streams came back from the journal
        for uid, c in done_before.items():
            assert router2.completions[uid].tokens == c.tokens
            assert router2.completions[uid].replica == "<journal>"
        # and the SAME journal path journals the rest: a second
        # recovery sees every stream complete
        rec2 = recover_journal(path)
        assert rec2.corrupt == 0 and rec2.gapped == 0
        for uid, toks in ref.items():
            assert rec2.entries[uid]["done"], uid
            assert rec2.entries[uid]["emitted"] == toks, uid


# ---------------------------------------------------------------------------
# cancel mid-speculation-window (regression for the hedge/deadline
# cancel path)
# ---------------------------------------------------------------------------


class TestCancelMidSpeculation:
    def test_cancel_mid_window_is_safe(self):
        from apex_tpu.models import GPTConfig, GPTModel
        from apex_tpu.serving.speculate import NGramDraftSource
        from apex_tpu.transformer import parallel_state

        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:1])
        try:
            model = GPTModel(GPTConfig(
                vocab_size=64, num_layers=2, hidden_size=32,
                num_attention_heads=4, max_position_embeddings=64,
                compute_dtype=jnp.float32, remat=False,
                attention_impl="xla"))
            params = model.init(jax.random.PRNGKey(0))
            PAGE, NEW, K, maxp = 4, 8, 3, 12
            pps = -(-(maxp + NEW) // PAGE)
            ccfg = KVCacheConfig(
                num_layers=2, num_heads=4, head_dim=8,
                num_pages=1 + 2 * pps, page_size=PAGE, max_seqs=2,
                pages_per_seq=pps, dtype=jnp.float32)
            fns = model.decode_fns(params, mesh, ccfg,
                                   max_prompt_len=maxp, speculate_k=K)
            # repetitive prompts so drafts actually accept (the cancel
            # must land while multi-token windows are in flight)
            rng = np.random.RandomState(3)
            prompts = []
            for n in (12, 11, 10):
                pat = rng.randint(1, 64, (4,))
                prompts.append([int(t) for t in np.tile(pat, 3)[:n]])
            reqs = [Request(uid=f"s{i}", prompt=list(p),
                            max_new_tokens=NEW)
                    for i, p in enumerate(prompts)]

            def batcher():
                return ContinuousBatcher(
                    fns.prefill, fns.decode, PagedKVCache(ccfg),
                    init_pools(ccfg), max_prompt_len=maxp,
                    harvest_every=3, spec_fn=fns.spec, speculate_k=K,
                    draft_source=NGramDraftSource(K))

            ref = {u: c.tokens
                   for u, c in batcher().run(list(reqs)).items()}

            b = batcher()
            import collections
            q = collections.deque(reqs)
            b.pump(q)                       # s0+s1 admitted, mid-stream
            assert b.live_slots == 2
            got = b.cancel("s0")
            # the victim's harvested tokens are a committed prefix
            assert got is not None
            assert got == ref["s0"][:len(got)]
            assert b.cancel("s0") is None   # idempotent: already gone
            while b.live_slots or q:
                b.pump(q)
            assert "s0" not in b.completions
            # survivors (including s2, admitted into the FREED slot)
            # are token-identical to the uncancelled reference
            assert b.completions["s1"].tokens == ref["s1"]
            assert b.completions["s2"].tokens == ref["s2"]
            # every page came back (shared prefix pages excepted: none
            # here — no prefix cache)
            assert (b.cache.allocator.num_free
                    == ccfg.num_pages - 1)
        finally:
            parallel_state.destroy_model_parallel()


# ---------------------------------------------------------------------------
# metrics report: the fault/recovery section
# ---------------------------------------------------------------------------


class TestFaultReportSection:
    def test_summarize_faults(self):
        from tools.metrics_report import format_report, summarize

        records = [
            {"kind": "event", "t": 1.0, "event": "replica_fault",
             "replica": "r0", "consecutive": 1, "error": "boom"},
            {"kind": "event", "t": 1.1, "event": "replica_fault",
             "replica": "r0", "consecutive": 2, "error": "boom"},
            {"kind": "event", "t": 1.2, "event": "replica_quarantined",
             "replica": "r0", "cause": "faults"},
            {"kind": "event", "t": 1.3, "event": "request_migrated",
             "uid": "a", "replica": "r1"},
            {"kind": "event", "t": 1.4, "event": "request_migrated",
             "uid": "b", "replica": "r1", "cause": "deadline"},
            {"kind": "event", "t": 1.5, "event": "deadline_miss",
             "uid": "b", "slo": "interactive", "retry": True},
            {"kind": "event", "t": 1.6, "event": "deadline_miss",
             "uid": "c", "slo": "interactive", "retry": False},
            {"kind": "event", "t": 1.7, "event": "hedge_spawn",
             "uid": "d", "replica": "r1", "primary": "r0"},
            {"kind": "event", "t": 1.8, "event": "hedge_win",
             "uid": "d", "replica": "r1"},
            {"kind": "event", "t": 1.9, "event": "brownout",
             "from_level": 0, "to_level": 2, "free_page_frac": 0.04,
             "queue_depth": 9},
            {"kind": "event", "t": 2.0, "event": "journal_replayed",
             "resumed": 3, "completed": 2, "corrupt": 1, "gapped": 0},
            {"kind": "event", "t": 2.1, "event": "trace_request",
             "uid": "b", "slo": "interactive", "reason": "eos"},
            {"kind": "event", "t": 2.2, "event": "trace_request",
             "uid": "c", "slo": "interactive", "reason": "deadline"},
        ]
        s = summarize(records)
        ft = s["faults"]
        assert ft["replica_faults"]["count"] == 2
        assert ft["replica_faults"]["by_replica"] == {"r0": 2}
        assert ft["quarantined"] == [{"replica": "r0",
                                      "cause": "faults"}]
        assert ft["migrations"]["by_cause"] == {
            "replica_dead": 1, "deadline": 1}
        assert ft["deadline_misses"] == {"count": 2, "retried": 1,
                                         "terminal": 1}
        assert ft["hedging"] == {"spawned": 1, "wins": 1, "losses": 0}
        assert ft["brownout"]["max_level"] == 2
        assert ft["journal_replays"][0]["resumed"] == 3
        att = ft["slo_attainment"]["interactive"]
        assert att == {"n": 2, "deadline_missed": 1,
                       "attainment": 0.5}
        text = format_report(s)
        assert "fault / recovery summary:" in text
        assert "quarantined: r0(faults)" in text
        assert "slo attainment 50.0%" in text
        # the timeline keeps the new fields
        tl = {e["event"]: e for e in s["events"]["timeline"]}
        assert tl["brownout"]["to_level"] == 2
        assert tl["replica_quarantined"]["cause"] == "faults"

    def test_load_gen_counts_deadline_and_hedge(self):
        from tools.load_gen import summarize_trace

        recs = [
            {"uid": "a", "slo": "interactive", "reason": "eos",
             "new_tokens": 3},
            {"uid": "b", "slo": "interactive", "reason": "deadline",
             "new_tokens": 1},
            {"uid": "c", "slo": "batch", "reason": "eos",
             "new_tokens": 2, "hedged": True},
        ]
        s = summarize_trace(recs)
        assert s["deadline_missed"] == 1
        assert s["hedged"] == 1
        assert s["completed"] == 3
