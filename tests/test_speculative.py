"""Speculative decoding: draft sources, the fused acceptance rule, the
verify step, and the speculative continuous-batching window end-to-end
on the tiny GPT — plus the multi-token failover contract.

The load-bearing claims, each pinned here:

- the n-gram draft source attributes hits to prompt-lookup vs
  self-repetition, prefers the MOST RECENT occurrence, caps at k, and
  never drafts from a context too short to match;
- ``spec_accept`` is greedy-exact (accepted prefix == argmax prefix
  match) and, for sampled rows, COUPLED to the plain sampler: row j's
  target is bitwise the token ``sample`` would draw with row j's key —
  the identity that makes every downstream gate exact, not statistical;
- ``verify_step`` with zero drafts degenerates to ``decode_step``
  (same logits, row 0), so the speculative path is a strict superset
  of the plain one;
- speculative greedy serving is token-identical to the plain decode
  path under 6-requests/2-slots admit/retire churn, including
  mid-verify EOS cuts; seeded SAMPLED serving is token-identical too,
  across admission orders (cross-replica determinism survives
  variable advances);
- rejected drafts roll back by length truncation: the pool pages a
  speculative run leaves at committed positions are bit-identical to
  a never-drafted run's, and the allocator's free count / refcounts
  match throughout;
- acceptance patterns change CONTENTS, never shapes — the verify step
  adds zero jit entries across request waves;
- the request log survives multi-token commits: ``record_progress``
  folds k-token jumps exactly, over-commit fails loudly at the
  recording boundary, and ``resume_request`` budget math is by token
  count; the replica-kill drill completes every request
  token-identical to an unkilled fleet WITH speculation on.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_tpu.fleet import FleetRouter, Replica, RequestLog, \
    resume_request
from apex_tpu.serving.kv_cache import (
    KVCacheConfig,
    PagedKVCache,
    init_pools,
)
from apex_tpu.serving.sampling import greedy, sample, spec_accept
from apex_tpu.serving.serve import ContinuousBatcher, Request
from apex_tpu.serving.speculate import (
    ModelDraftSource,
    NGramDraftSource,
    NullDraftSource,
    chain_tree,
    offramp_tree,
    tree_ancestors,
    tree_chain_rows,
    tree_depths,
    tree_max_depth,
    validate_tree,
)


# ---------------------------------------------------------------------------
# draft sources: pure host, no model
# ---------------------------------------------------------------------------


class TestNGramDraftSource:
    def test_prompt_lookup_attribution(self):
        src = NGramDraftSource(3, max_ngram=3)
        # tail [1,2,3] recurs at the prompt's start: continuation is
        # the tokens that followed it there
        toks, tag = src.draft([1, 2, 3, 4, 5, 1, 2, 3], prompt_len=8)
        assert toks == [4, 5, 1]
        assert tag == "prompt_lookup"

    def test_ngram_attribution_in_generated_region(self):
        src = NGramDraftSource(2, max_ngram=3)
        ctx = [9, 9] + [1, 2, 3, 1, 2, 3, 1, 2]
        toks, tag = src.draft(ctx, prompt_len=2)
        assert toks == [3, 1]
        assert tag == "ngram"          # the match lives in generation

    def test_most_recent_occurrence_wins(self):
        src = NGramDraftSource(1, max_ngram=2)
        # [1,2] occurs twice with different continuations: the drafter
        # must follow the LATEST one (recency tracks the model's loop)
        toks, _ = src.draft([1, 2, 5, 1, 2, 7, 1, 2], prompt_len=8)
        assert toks == [7]

    def test_no_match_and_short_context_draft_nothing(self):
        src = NGramDraftSource(4)
        assert src.draft([1, 2, 3, 4, 5], prompt_len=5) == ([], None)
        assert src.draft([1], prompt_len=1) == ([], None)
        assert src.draft([], prompt_len=0) == ([], None)

    def test_continuation_capped_at_k(self):
        src = NGramDraftSource(2, max_ngram=2)
        toks, _ = src.draft([5, 6, 7, 8, 9, 5, 6], prompt_len=7)
        assert toks == [7, 8]          # not [7, 8, 9]

    def test_validation(self):
        with pytest.raises(ValueError):
            NGramDraftSource(0)
        with pytest.raises(ValueError):
            NGramDraftSource(2, max_ngram=0)

    def test_null_source_never_drafts(self):
        assert NullDraftSource().draft([1, 2, 3], 3) == ([], None)

    def test_model_draft_source_validation(self):
        # validation fires before any model machinery is touched
        with pytest.raises(ValueError, match="k must be"):
            ModelDraftSource(object(), {}, None, None, k=0)
        with pytest.raises(ValueError, match="arbitrary trees"):
            ModelDraftSource(object(), {}, None, None, k=2,
                             tree=(-1, 0, 0, 1))


# ---------------------------------------------------------------------------
# spec_accept: the fused acceptance rule
# ---------------------------------------------------------------------------


def _one_hot_logits(targets, vocab=32):
    rows = np.full((len(targets), vocab), -5.0, np.float32)
    for j, t in enumerate(targets):
        rows[j, t] = 5.0
    return jnp.asarray(rows)


class TestSpecAccept:
    def test_greedy_accepts_exact_prefix_match(self):
        logits = _one_hot_logits([5, 6, 7, 8])
        targets, n_acc = spec_accept(
            logits, jnp.asarray([5, 6, 9]), jnp.int32(3), None)
        assert list(np.asarray(targets)) == [5, 6, 7, 8]
        assert int(n_acc) == 2          # 5, 6 match; 9 != 7 stops it

    def test_greedy_full_and_zero_acceptance(self):
        logits = _one_hot_logits([5, 6, 7, 8])
        _, full = spec_accept(
            logits, jnp.asarray([5, 6, 7]), jnp.int32(3), None)
        assert int(full) == 3
        _, none = spec_accept(
            logits, jnp.asarray([9, 6, 7]), jnp.int32(3), None)
        assert int(none) == 0

    def test_draft_len_masks_padding_rows(self):
        logits = _one_hot_logits([5, 6, 7, 8])
        # rows past draft_len "match" by accident (padding 0 vs row 1
        # target) — they must not count
        targets, n_acc = spec_accept(
            logits, jnp.asarray([5, 6, 7]), jnp.int32(1), None)
        assert int(n_acc) == 1
        assert list(np.asarray(targets)) == [5, 6, 7, 8]

    def test_sampled_rows_are_coupled_to_plain_sample(self):
        """Row j's target must be BITWISE the token ``sample`` draws
        from row j's logits with row j's key — the coupling that turns
        distribution preservation into an exact identity."""
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(4, 32).astype(np.float32))
        keys = jax.random.split(jax.random.PRNGKey(42), 4)
        targets, _ = spec_accept(
            logits, jnp.zeros((3,), jnp.int32), jnp.int32(0), keys,
            temperature=0.7, top_k=8, top_p=0.9)
        want = [int(sample(logits[j][None], keys[j], 0.7, 8, 0.9)[0])
                for j in range(4)]
        assert list(np.asarray(targets)) == want

    def test_greedy_targets_are_argmax_bitwise(self):
        rng = np.random.RandomState(1)
        logits = jnp.asarray(rng.randn(5, 64).astype(np.float32))
        targets, _ = spec_accept(
            logits, jnp.zeros((4,), jnp.int32), jnp.int32(0), None)
        assert np.array_equal(np.asarray(targets),
                              np.asarray(greedy(logits)))

    def test_validation(self):
        logits = _one_hot_logits([1, 2])
        with pytest.raises(ValueError, match="keys"):
            spec_accept(logits, jnp.asarray([1]), jnp.int32(1), None,
                        temperature=0.5)
        with pytest.raises(ValueError):
            spec_accept(logits[0], jnp.asarray([1]), jnp.int32(1),
                        None)
        with pytest.raises(ValueError):
            spec_accept(logits, jnp.asarray([1, 2]), jnp.int32(1),
                        None)


# ---------------------------------------------------------------------------
# the tiny-GPT serving stack with speculation
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def spec_setup():
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
    params = model.init(jax.random.PRNGKey(0))
    # repetitive prompts (tiled 4-cycles, ragged lengths) so the
    # n-gram drafter gets real acceptance even on untrained weights —
    # the identity gates below hold for ANY acceptance pattern, but a
    # pattern of all-rejects would test less
    rng = np.random.RandomState(3)
    prompts, plens = [], [12, 11, 9, 12, 10, 8]
    for i in range(6):
        pat = rng.randint(1, 64, (4,))
        prompts.append([int(t) for t in np.tile(pat, 3)[:plens[i]]])
    yield mesh, model, params, prompts, 12
    parallel_state.destroy_model_parallel()


PAGE, NEW, K = 4, 12, 3


def _batcher(setup, *, spec=True, temperature=0.0, draft=None,
             eos_id=None, max_seqs=2, logger=None, tree=None,
             draft_model=None):
    mesh, model, params, prompts, maxp = setup
    pps = -(-(maxp + NEW) // PAGE)
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=4, head_dim=8,
        num_pages=1 + max_seqs * pps, page_size=PAGE,
        max_seqs=max_seqs, pages_per_seq=pps, dtype=jnp.float32)
    fns = model.decode_fns(
        params, mesh, ccfg, max_prompt_len=maxp,
        temperature=temperature, eos_id=eos_id,
        speculate_k=K if spec else None,
        spec_tree=tree, draft_model=draft_model)
    kw = {}
    if spec:
        # a bound draft_model rides in on fns.spec; otherwise the
        # explicit source (or the n-gram default) drafts
        src = (None if draft_model is not None
               else draft or NGramDraftSource(K))
        kw = dict(spec_fn=fns.spec, speculate_k=K, draft_source=src)
    return ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), init_pools(ccfg),
        max_prompt_len=maxp, harvest_every=3, eos_id=eos_id,
        logger=logger, **kw), fns


def _reqs(prompts, *, new=NEW, seed=None, tag=""):
    return [Request(uid=f"{tag}{i}", prompt=list(p),
                    max_new_tokens=new,
                    seed=None if seed is None else seed + i)
            for i, p in enumerate(prompts)]


class TestSpeculativeServing:
    def test_greedy_identity_under_churn(self, spec_setup):
        """6 requests through 2 slots: every speculative completion
        (tokens AND finish reason) matches the plain decode path's."""
        prompts = spec_setup[3]
        plain, _ = _batcher(spec_setup, spec=False)
        ref = plain.run(_reqs(prompts))
        spec, _ = _batcher(spec_setup)
        got = spec.run(_reqs(prompts))
        for i in range(6):
            uid = str(i)
            assert got[uid].tokens == ref[uid].tokens, uid
            assert got[uid].reason == ref[uid].reason, uid
        # the identity gate is only meaningful if drafts were accepted
        assert spec.spec_stats["accepted"] > 0
        assert spec.spec_stats["committed"] > spec.spec_stats["steps"]

    def test_eos_cut_inside_verify_window(self, spec_setup):
        """An EOS landing mid-verify must truncate the commit exactly
        where the plain path stops — committed THROUGH the eos, never
        past it."""
        prompts = spec_setup[3]
        plain, _ = _batcher(spec_setup, spec=False)
        flat = [t for c in plain.run(_reqs(prompts)).values()
                for t in c.tokens]
        eos = max(set(flat), key=flat.count)
        plain_e, _ = _batcher(spec_setup, spec=False, eos_id=eos)
        ref = plain_e.run(_reqs(prompts))
        spec_e, _ = _batcher(spec_setup, eos_id=eos)
        got = spec_e.run(_reqs(prompts))
        assert any(c.reason == "eos" for c in ref.values())
        for i in range(6):
            uid = str(i)
            assert got[uid].tokens == ref[uid].tokens, uid
            assert got[uid].reason == ref[uid].reason, uid

    def test_seeded_sampled_identity_across_orders(self, spec_setup):
        """Seeded sampled speculative streams equal plain sampling's,
        and survive a different admission order — the cross-replica
        determinism the failover contract needs, now under variable
        multi-token advances."""
        prompts = spec_setup[3]
        plain, _ = _batcher(spec_setup, spec=False, temperature=0.8)
        ref = plain.run(_reqs(prompts, seed=100))
        spec, _ = _batcher(spec_setup, temperature=0.8)
        got = spec.run(_reqs(prompts, seed=100))
        spec2, _ = _batcher(spec_setup, temperature=0.8)
        got2 = spec2.run(list(reversed(_reqs(prompts, seed=100))))
        for i in range(6):
            uid = str(i)
            assert got[uid].tokens == ref[uid].tokens, uid
            assert got2[uid].tokens == ref[uid].tokens, uid

    def test_null_draft_source_degenerates_to_plain(self, spec_setup):
        prompts = spec_setup[3]
        plain, _ = _batcher(spec_setup, spec=False)
        ref = plain.run(_reqs(prompts))
        null_b, _ = _batcher(spec_setup, draft=NullDraftSource())
        got = null_b.run(_reqs(prompts))
        for i in range(6):
            assert got[str(i)].tokens == ref[str(i)].tokens, i
        assert null_b.spec_stats["drafted"] == 0
        # every verify step still commits exactly one token per slot
        assert (null_b.spec_stats["committed"]
                == null_b.spec_stats["slot_steps"])

    def test_zero_new_jit_entries_across_acceptance_patterns(
            self, spec_setup):
        """Wave 2's prompts (random, mostly-rejecting) produce commit
        patterns wave 1 (repetitive, mostly-accepting) never saw; the
        verify step must not add a single jit entry."""
        prompts = spec_setup[3]
        spec, fns = _batcher(spec_setup)
        spec.run(_reqs(prompts))
        size = fns.spec_jit._cache_size()
        assert size <= 2, size
        rng = np.random.RandomState(11)
        adv = [[int(t) for t in rng.randint(1, 64, (12,))]
               for _ in range(4)]
        spec.run(_reqs(adv, tag="w2-"))
        assert fns.spec_jit._cache_size() == size
        assert fns.prefill_jit._cache_size() <= 2

    def test_rollback_leaves_pool_bits_identical_to_never_drafted(
            self, spec_setup):
        """Rejection is length-truncation, not data repair: at every
        COMMITTED position the pool a drafting run leaves is
        bit-identical to a never-drafted (NullDraftSource) run's, and
        the allocator ends fully recycled in both."""
        prompts = spec_setup[3][:2]

        def run(draft):
            b, _ = _batcher(spec_setup, draft=draft)
            snaps = {}
            orig = b._retire

            def spy(done_h, t_h):
                snaps["pt"] = np.array(b.cache.page_table).copy()
                snaps["lengths"] = np.array(b.cache.lengths).copy()
                snaps["free"] = b.cache.allocator.num_free
                orig(done_h, t_h)

            b._retire = spy
            comps = b.run(_reqs(prompts))
            return b, snaps, comps

        ng_b, ng_s, ng_c = run(NGramDraftSource(K))
        nl_b, nl_s, nl_c = run(NullDraftSource())
        assert ng_b.spec_stats["accepted"] > 0   # drafting happened
        for i in range(2):
            assert ng_c[str(i)].tokens == nl_c[str(i)].tokens, i
        # same allocation history -> same physical pages, lengths, and
        # mid-flight free count
        assert np.array_equal(ng_s["pt"], nl_s["pt"])
        assert np.array_equal(ng_s["lengths"], nl_s["lengths"])
        assert ng_s["free"] == nl_s["free"]
        for slot in range(2):
            pages = [p for p in ng_s["pt"][slot] if p != 0]
            ln = int(ng_s["lengths"][slot])
            for a, b_ in zip(jax.tree.leaves(ng_b.pools),
                             jax.tree.leaves(nl_b.pools)):
                # (layers, pages, heads, page_size, dim) -> rows in
                # logical position order, truncated at the committed
                # length — the only region the contract covers
                ga = np.moveaxis(np.asarray(a)[:, pages], 3, 2)
                gb = np.moveaxis(np.asarray(b_)[:, pages], 3, 2)
                ga = ga.reshape(ga.shape[0], -1, *ga.shape[3:])[:, :ln]
                gb = gb.reshape(gb.shape[0], -1, *gb.shape[3:])[:, :ln]
                assert np.array_equal(ga, gb), slot
        # both runs end fully recycled
        npages = ng_b.cache.config.num_pages
        assert ng_b.cache.allocator.num_free == npages - 1
        assert nl_b.cache.allocator.num_free == npages - 1

    @pytest.mark.parametrize("position", ["learned", "rope"])
    @pytest.mark.parametrize(
        "case", ["zero_drafts", "decode_is_one_row", "chunk_is_a_chain"])
    def test_verify_step_with_zero_drafts_matches_decode_step(
            self, spec_setup, case, position):
        """The three adapters of ``GPTModel._paged_rows`` agree where
        their shapes meet.  ``zero_drafts``: row 0 of a draft-free
        verify step IS the plain decode step (argmax-identical,
        numerically tight).  ``decode_is_one_row``: ``decode_step`` is
        ``verify_step`` at ``R = 1``; ``chunk_is_a_chain``:
        ``prefill_chunk`` on ``(1, C)`` rows is ``verify_step`` for one
        slot with ``R = C`` chain rows on the same pools — the same
        pool bits and the same logits, to the last bit (the chunk's
        LM head runs on its one row, the chain's on all C: the same
        hidden row through another matmul shape, so a ulp there)."""
        from jax.sharding import PartitionSpec as P

        from apex_tpu.models import GPTConfig, GPTModel

        mesh, model, params, prompts, maxp = spec_setup
        if position == "rope":
            model = GPTModel(GPTConfig(
                vocab_size=64, num_layers=2, hidden_size=32,
                num_attention_heads=4, max_position_embeddings=64,
                compute_dtype=jnp.float32, remat=False,
                attention_impl="xla", position_embedding="rope"))
            params = model.init(jax.random.PRNGKey(0))
        # a LIVE cache state (retired tables alias the null-page sink,
        # which the two paths fill with different scratch): admit two
        # slots and prefill their prompts explicitly
        pps = -(-(maxp + NEW) // PAGE)
        ccfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8,
            num_pages=1 + 2 * pps, page_size=PAGE, max_seqs=2,
            pages_per_seq=pps, dtype=jnp.float32)
        fns = model.decode_fns(params, mesh, ccfg,
                               max_prompt_len=maxp, speculate_k=K)
        cache = PagedKVCache(ccfg)
        pools = init_pools(ccfg)
        S = 2
        firsts = []
        for slot in range(S):
            cache.admit(slot, maxp + NEW)
            padded = np.zeros((1, maxp), np.int32)
            padded[0, :len(prompts[slot])] = prompts[slot]
            pools, first = fns.prefill(
                pools, jnp.asarray(padded),
                jnp.int32(len(prompts[slot])),
                jnp.asarray(cache.page_table[slot]),
                jax.random.PRNGKey(slot))
            firsts.append(int(jax.device_get(first)))
        pt = jnp.asarray(cache.page_table)
        toks = jnp.asarray(firsts, jnp.int32)
        lens = jnp.asarray([len(prompts[0]), len(prompts[1])],
                           jnp.int32)
        C = PAGE
        start = 2 * C       # slot 1's third chunk of 4, other tokens
        chunk = jnp.asarray(
            [[t + 1 for t in (prompts[1] + [0] * C)[start:start + C]]],
            jnp.int32) % 64

        def both(p, pools, toks, lens, pt):
            active = jnp.ones((S,), bool)
            if case == "chunk_is_a_chain":
                # rows at or past the prompt's end are padding in both
                l1, p1 = model.prefill_chunk(
                    p, chunk, start, lens[1], 0, pt[1], pools)
                valid = (start + jnp.arange(C) < lens[1])[None]
                l2, p2 = model.verify_step(
                    p, chunk, jnp.full((1,), start), active[:1], valid,
                    pt[1:], pools)
                return l1, l2[0, lens[1] - 1 - start], p1, p2
            l1, p1 = model.decode_step(p, toks, lens, active, pt, pools)
            R = 1 if case == "decode_is_one_row" else K + 1
            rows = jnp.concatenate(
                [toks[:, None], jnp.zeros((S, R - 1), jnp.int32)], axis=1)
            valid = jnp.broadcast_to(jnp.arange(R)[None] <= 0, (S, R))
            l2, p2 = model.verify_step(p, rows, lens, active, valid,
                                       pt, pools)
            return l1, l2[:, 0], p1, p2

        specs = model.param_specs()
        # the serving layout: head-sharded pools, vocab-parallel logits
        pool_specs = jax.tree.map(
            lambda _: P(None, None, "tp", None, None), pools)
        logit_spec = P("tp") if case == "chunk_is_a_chain" \
            else P(None, "tp")
        run = jax.jit(jax.shard_map(
            both, mesh=mesh,
            in_specs=(specs, pool_specs, P(), P(), P()),
            out_specs=(logit_spec, logit_spec, pool_specs, pool_specs)))
        l1, l2, p1, p2 = jax.device_get(
            run(params, pools, toks, lens, pt))
        assert np.array_equal(np.argmax(l1, -1), np.argmax(l2, -1))
        if case == "zero_drafts":
            # four rows against one: another attention shape
            np.testing.assert_allclose(l1, l2, rtol=0, atol=1e-5)
            return
        if case == "chunk_is_a_chain":
            np.testing.assert_allclose(l1, l2, rtol=0, atol=1e-6)
        else:
            np.testing.assert_array_equal(l1, l2)
        for name in p1:
            # page 0 is the null page, the sink of masked rows
            np.testing.assert_array_equal(p1[name][:, 1:], p2[name][:, 1:])
            assert not np.array_equal(p1[name][:, 1:],
                                      jax.device_get(pools[name])[:, 1:])

    def test_spec_telemetry_reaches_metrics_report(
            self, spec_setup, tmp_path):
        """spec_accept events land in the jsonl stream and the report
        renders the speculation scoreboard — histogram, per-source hit
        rates, wasted-verify fraction — from them alone."""
        from apex_tpu.telemetry.metrics import MetricsLogger

        import tools.metrics_report as mr

        prompts = spec_setup[3]
        jsonl = str(tmp_path / "spec.jsonl")
        logger = MetricsLogger(jsonl_path=jsonl, console=False)
        b, _ = _batcher(spec_setup, logger=logger)
        b.run(_reqs(prompts))
        logger.close()
        summary = mr.summarize(mr.load_records(jsonl))
        sp = summary["serving"]["speculation"]
        assert sp["verify_steps"] == b.spec_stats["steps"]
        assert sp["drafted"] == b.spec_stats["drafted"]
        assert sp["accepted"] == b.spec_stats["accepted"]
        assert sp["committed"] == b.spec_stats["committed"]
        assert sp["committed_per_slot_step"] > 1.0
        assert 0.0 <= sp["wasted_verify_fraction"] <= 1.0
        assert sum(sp["accepted_per_step_hist"].values()) \
            == b.spec_stats["slot_steps"]
        assert any(src in sp["by_source"]
                   for src in ("ngram", "prompt_lookup"))
        for src, rec in sp["by_source"].items():
            assert 0.0 <= rec["hit_rate"] <= 1.0
        assert sp["offramp_commits"] == b.spec_stats["offramp"]
        assert sp["draft_wall_s"] >= 0.0
        assert 0.0 <= sp["draft_wall_fraction"] < 1.0
        text = mr.format_report(summary)
        assert "speculation:" in text
        assert "tokens/slot-step" in text

    def test_batcher_spec_validation(self, spec_setup):
        mesh, model, params, prompts, maxp = spec_setup
        pps = -(-(maxp + NEW) // PAGE)
        ccfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8,
            num_pages=1 + 2 * pps, page_size=PAGE, max_seqs=2,
            pages_per_seq=pps, dtype=jnp.float32)
        fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=maxp,
                               speculate_k=K)
        base = dict(max_prompt_len=maxp, harvest_every=3)

        def make(**kw):
            return ContinuousBatcher(
                fns.prefill, fns.decode, PagedKVCache(ccfg),
                init_pools(ccfg), **base, **kw)

        with pytest.raises(ValueError, match="speculate_k"):
            make(spec_fn=fns.spec)
        with pytest.raises(ValueError, match="spec_fn"):
            make(speculate_k=K)
        with pytest.raises(ValueError, match="speculate_k"):
            make(spec_fn=fns.spec, speculate_k=K + 1)
        with pytest.raises(ValueError, match="draft_source"):
            make(draft_source=NGramDraftSource(K))
        with pytest.raises(TypeError, match="DraftSource"):
            model.decode_fns(params, mesh, ccfg, max_prompt_len=maxp,
                             speculate_k=K, draft_model=object())
        with pytest.raises(ValueError, match="speculate_k"):
            model.decode_fns(params, mesh, ccfg, max_prompt_len=maxp,
                             spec_tree=chain_tree(K))
        with pytest.raises(ValueError, match="max depth"):
            model.decode_fns(params, mesh, ccfg, max_prompt_len=maxp,
                             speculate_k=K + 1,
                             spec_tree=chain_tree(K))


# ---------------------------------------------------------------------------
# candidate trees: helpers, the coupled tree walk, tree serving
# ---------------------------------------------------------------------------


class TestTreeHelpers:
    def test_shapes_and_depths(self):
        assert chain_tree(3) == (-1, 0, 1, 2)
        assert offramp_tree(3) == (-1, 0, 1, 2, 0, 1, 2)
        assert tree_depths(offramp_tree(3)) == (0, 1, 2, 3, 1, 2, 3)
        assert tree_max_depth(offramp_tree(3)) == 3
        assert tree_chain_rows(offramp_tree(3)) == (1, 2, 3)
        assert tree_chain_rows(chain_tree(2)) == (1, 2)

    def test_ancestor_matrix(self):
        A = np.asarray(tree_ancestors(offramp_tree(2)))  # (-1,0,1,0,1)
        assert (np.diag(A) == 1).all()          # write-before-attend
        assert np.triu(A, 1).sum() == 0         # topological order
        assert (A[:, 0] == 1).all()             # root in every path
        # off-ramp row 3 hangs off the ROOT: it must not see the
        # chain rows it is an alternative to
        assert A[3, 1] == 0 and A[3, 2] == 0
        # off-ramp row 4 hangs off chain row 1: sees it, not row 2
        assert A[4, 1] == 1 and A[4, 2] == 0

    def test_validate_tree_rejections(self):
        with pytest.raises(ValueError):
            validate_tree(())
        with pytest.raises(ValueError):
            validate_tree((0,))                # root's parent is -1
        with pytest.raises(ValueError):
            validate_tree((-1, 1))             # parent precedes child
        with pytest.raises(ValueError):
            validate_tree((-1, -1))            # ONE root


class TestSpecAcceptTree:
    V = 16

    def _logits(self, rows, seed=0):
        return jax.random.normal(jax.random.PRNGKey(seed),
                                 (rows, self.V), jnp.float32)

    def _keys(self, rows):
        return jnp.stack([jax.random.PRNGKey(100 + i)
                          for i in range(rows)])

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_chain_tree_reduces_to_spec_accept(self, temperature):
        """A chain-shaped parents tuple must reproduce spec_accept
        bit-for-bit — the tree walk is a strict generalization."""
        from apex_tpu.serving.sampling import spec_accept_tree

        k = 3
        logits = self._logits(k + 1, seed=1)
        keys = self._keys(k + 1)
        t_ref = (np.asarray(jnp.argmax(logits, axis=-1))
                 if temperature == 0.0 else
                 np.asarray(jax.vmap(
                     lambda l, kk: sample(l[None], kk, temperature)[0]
                 )(logits, keys)))
        drafts = jnp.asarray(
            [t_ref[0], t_ref[1], (t_ref[2] + 1) % self.V], jnp.int32)
        out, n, path = spec_accept_tree(
            logits, drafts, chain_tree(k), jnp.ones((k,), bool), keys,
            temperature)
        t_chain, n_chain = spec_accept(
            logits, drafts, jnp.int32(k), keys, temperature)
        assert int(n) == int(n_chain) == 2
        nc = int(n) + 1
        assert (np.asarray(out)[:nc].tolist()
                == np.asarray(t_chain)[:nc].tolist())
        assert np.asarray(path).tolist() == [0, 1, 2, 2]  # stalls

    def test_offramp_rescues_rejected_chain(self):
        from apex_tpu.serving.sampling import spec_accept_tree

        tree = offramp_tree(2)                 # (-1, 0, 1, 0, 1)
        logits = self._logits(5, seed=3)
        g = np.asarray(jnp.argmax(logits, axis=-1))
        # chain row 1 misses the target; off-ramp row 3 carries it
        drafts = jnp.asarray(
            [(g[0] + 1) % self.V, 0, g[0], (g[1] + 1) % self.V],
            jnp.int32)
        out, n, path = spec_accept_tree(
            logits, drafts, tree, jnp.ones((4,), bool), None)
        assert int(n) == 1
        p = np.asarray(path).tolist()
        assert p[0] == 0 and p[1] == 3
        o = np.asarray(out)
        # committed token = the coupled draw; correction comes from
        # the ACCEPTED node's logits row
        assert o[0] == g[0] and o[1] == g[3]

    def test_equal_token_siblings_resolve_first_in_row_order(self):
        from apex_tpu.serving.sampling import spec_accept_tree

        tree = offramp_tree(2)
        logits = self._logits(5, seed=4)
        g = np.asarray(jnp.argmax(logits, axis=-1))
        drafts = jnp.asarray([g[0], 0, g[0], 0], jnp.int32)
        out, n, path = spec_accept_tree(
            logits, drafts, tree, jnp.ones((4,), bool), None)
        # both depth-1 candidates carry the target token: the CHAIN
        # row wins (committed token is identical either way)
        assert np.asarray(path).tolist()[1] == 1

    def test_invalid_nodes_never_accepted(self):
        from apex_tpu.serving.sampling import spec_accept_tree

        tree = offramp_tree(2)
        logits = self._logits(5, seed=5)
        g = np.asarray(jnp.argmax(logits, axis=-1))
        drafts = jnp.asarray([g[0], g[1], g[0], g[1]], jnp.int32)
        out, n, _ = spec_accept_tree(
            logits, drafts, tree, jnp.zeros((4,), bool), None)
        assert int(n) == 0
        assert int(np.asarray(out)[0]) == g[0]  # the correction draw


class TestTreeServing:
    @pytest.mark.parametrize(
        "tree_fn", [chain_tree, offramp_tree],
        ids=["chain", "offramp"])
    def test_greedy_identity_both_tree_shapes(self, spec_setup,
                                              tree_fn):
        """Tree-verified greedy serving under 6-requests/2-slots churn
        is token-identical to plain decode, for both tree shapes."""
        prompts = spec_setup[3]
        plain, _ = _batcher(spec_setup, spec=False)
        ref = plain.run(_reqs(prompts))
        b, _ = _batcher(spec_setup, tree=tree_fn(K))
        got = b.run(_reqs(prompts))
        for i in range(6):
            uid = str(i)
            assert got[uid].tokens == ref[uid].tokens, uid
            assert got[uid].reason == ref[uid].reason, uid
        assert b.spec_stats["accepted"] > 0

    def test_seeded_sampled_identity_offramp(self, spec_setup):
        """Seeded SAMPLED streams through the off-ramp tree equal
        plain sampling's — the coupled walk preserves the per-slot
        absolute-position key schedule exactly."""
        prompts = spec_setup[3]
        plain, _ = _batcher(spec_setup, spec=False, temperature=0.8)
        ref = plain.run(_reqs(prompts, seed=100))
        b, _ = _batcher(spec_setup, tree=offramp_tree(K),
                        temperature=0.8)
        got = b.run(_reqs(prompts, seed=100))
        for i in range(6):
            assert got[str(i)].tokens == ref[str(i)].tokens, i

    def test_tree_shapes_never_change_jit_entries(self, spec_setup):
        """Waves with different acceptance/tree-draft patterns change
        CONTENTS, never shapes: zero jit growth after warmup."""
        prompts = spec_setup[3]
        b, fns = _batcher(spec_setup, tree=offramp_tree(K))
        b.run(_reqs(prompts[:2]))
        warm = fns.spec_jit._cache_size()
        b.run(_reqs(prompts, tag="w2-"))
        b.run(_reqs(list(reversed(prompts)), tag="w3-"))
        assert fns.spec_jit._cache_size() == warm

    def test_draft_source_rides_the_compiled_step(self, spec_setup):
        """decode_fns(draft_model=...) stamps the source onto spec;
        the batcher picks it up without an explicit draft_source."""
        mesh, model, params, prompts, maxp = spec_setup
        ds = NGramDraftSource(K)
        b, fns = _batcher(spec_setup, draft_model=ds)
        assert fns.draft_source is ds
        assert b.draft_source is ds

    def test_tree_mismatch_rejected(self, spec_setup):
        """A draft source built for one tree cannot drive a spec step
        compiled for another (or for a chain)."""

        class _TreeSrc(NGramDraftSource):
            tree = offramp_tree(K)

        with pytest.raises(ValueError, match="tree"):
            _batcher(spec_setup, tree=chain_tree(K),
                     draft=_TreeSrc(K))
        with pytest.raises(ValueError, match="tree"):
            _batcher(spec_setup, draft=_TreeSrc(K))


class TestModelDraftServing:
    def _source(self, setup, tree=None):
        mesh, model, params, prompts, maxp = setup
        pps = -(-(maxp + NEW + K) // PAGE)
        dcfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8,
            num_pages=1 + 2 * pps, page_size=PAGE, max_seqs=2,
            pages_per_seq=pps, dtype=jnp.float32)
        # weight_block=16: the tiny model's fused qkv rows (96) must
        # tile 2*block for the packed int4 halves
        return ModelDraftSource(model, params, mesh, dcfg, k=K,
                                tree=tree, weight_dtype="int4",
                                weight_block=16)

    def test_greedy_identity_with_draft_model(self, spec_setup):
        """A real int4 draft model drafting into the verify step keeps
        greedy serving token-identical to plain decode — and actually
        accepts (the draft model IS the target here, quantized)."""
        prompts = spec_setup[3]
        plain, _ = _batcher(spec_setup, spec=False)
        ref = plain.run(_reqs(prompts))
        b, _ = _batcher(spec_setup, draft_model=self._source(
            spec_setup))
        got = b.run(_reqs(prompts))
        for i in range(6):
            uid = str(i)
            assert got[uid].tokens == ref[uid].tokens, uid
            assert got[uid].reason == ref[uid].reason, uid
        st = b.spec_stats
        assert st["by_source"]["draft_model"]["accepted"] > 0
        assert st["draft_s"] > 0.0

    def test_tree_draft_model_identity_and_stream_bytes(
            self, spec_setup):
        """Off-ramp tree drafting from the int4 draft model: identity
        holds and the draft's weight stream is a fraction of the
        full-precision pool's."""
        prompts = spec_setup[3]
        plain, _ = _batcher(spec_setup, spec=False)
        ref = plain.run(_reqs(prompts))
        ds = self._source(spec_setup, tree=offramp_tree(K))
        b, fns = _batcher(spec_setup, tree=offramp_tree(K),
                          draft_model=ds)
        got = b.run(_reqs(prompts))
        for i in range(6):
            assert got[str(i)].tokens == ref[str(i)].tokens, i
        assert ds.weight_dtype == "int4"
        assert ds.weight_stream_bytes < fns.weight_stream_bytes

    def test_draft_is_pure_function_of_context(self, spec_setup):
        """Drafting twice from the same context — cold and through the
        per-slot KV memoization — returns identical tokens (the
        failover-replay requirement)."""
        ds = self._source(spec_setup, tree=offramp_tree(K))
        ctx = [3, 7, 11, 5, 3, 7, 11, 5, 3, 7]
        first, src = ds.draft(ctx, len(ctx))
        assert src == "draft_model" and len(first) == 2 * K
        again, _ = ds.draft(ctx, len(ctx))          # memoized prefix
        assert again == first
        cold = self._source(spec_setup, tree=offramp_tree(K))
        fresh, _ = cold.draft(ctx, len(ctx))
        assert fresh == first


# ---------------------------------------------------------------------------
# failover under multi-token advances
# ---------------------------------------------------------------------------


class TestFailoverMultiToken:
    def _log(self, new=10):
        log = RequestLog()
        req = Request(uid="u", prompt=[1, 2, 3], max_new_tokens=new,
                      seed=7)
        log.admit(req, "interactive", "r0", 0.0)
        return log, req

    def test_multi_token_jumps_fold_exactly(self):
        """progress() may grow by any count between harvests (a verify
        step commits up to k+1); the log stores streams, so resume
        math stays count-exact."""
        log, req = self._log()
        log.record_progress("r0", {"u": [4, 5, 6]}, 1.0)
        log.record_progress("r0", {"u": [4, 5, 6, 7, 8, 9, 1]}, 2.0)
        e = log.get("u")
        assert e.emitted == [4, 5, 6, 7, 8, 9, 1]
        log.reassign("u", "r1")
        resumed = resume_request(e)
        assert resumed.prompt == [1, 2, 3, 4, 5, 6, 7, 8, 9, 1]
        assert resumed.max_new_tokens == 3
        assert resumed.seed == 7

    def test_over_commit_fails_at_recording_boundary(self):
        log, req = self._log(new=4)
        with pytest.raises(ValueError, match="over-committed"):
            log.record_progress("r0", {"u": [1, 2, 3, 4, 5]}, 1.0)
        log2, _ = self._log(new=4)
        with pytest.raises(ValueError, match="over-committed"):
            log2.complete("u", [1, 2, 3, 4, 5], "budget", 1.0)

    def test_exact_budget_commit_is_legal(self):
        log, req = self._log(new=4)
        log.record_progress("r0", {"u": [1, 2, 3, 4]}, 1.0)
        e = log.complete("u", [1, 2, 3, 4], "budget", 2.0)
        assert e.emitted == [1, 2, 3, 4]

    def test_kill_drill_under_speculation(self, spec_setup):
        """r0 dies after 2 windows with speculative replicas: every
        request completes, >= 1 migrates, streams and budgets are
        identical to an unkilled speculative fleet."""
        mesh, model, params, prompts, maxp = spec_setup
        # replay headroom: a migrated request re-admits with
        # prompt + emitted as its prompt, so max_prompt_len must cover
        # len(prompt) + max_new - 1
        new_f, maxp_f = 6, 18
        pps = -(-(maxp_f + new_f) // PAGE)
        ccfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8,
            num_pages=1 + 4 * pps, page_size=PAGE, max_seqs=2,
            pages_per_seq=pps, dtype=jnp.float32)
        fns = model.decode_fns(params, mesh, ccfg,
                               max_prompt_len=maxp_f, speculate_k=K)

        def replicas():
            return [
                Replica(f"r{i}", ContinuousBatcher(
                    fns.prefill, fns.decode, PagedKVCache(ccfg),
                    init_pools(ccfg), max_prompt_len=maxp_f,
                    harvest_every=2, spec_fn=fns.spec, speculate_k=K,
                    draft_source=NGramDraftSource(K)))
                for i in range(2)
            ]

        reqs = [Request(uid=f"u{i}", prompt=list(prompts[i % 6]),
                        max_new_tokens=new_f) for i in range(8)]

        def run(fail):
            router = FleetRouter(replicas())
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
            assert len(drill.completions[uid].tokens) <= new_f
        assert any(c.replays > 0 for c in drill.completions.values())
        # the drill actually exercised speculation, not a plain path
        assert any(r.batcher.spec_stats["committed"] > 0
                   for r in drill.replicas)
