"""DeepSeek-V3.2 on the serving path, at a small size on the CPU, against
the benchmark's plain float32 reference (``benchmarks/reference/
deepseek_v32.py``, imported, nothing of the program in it): the whole
forward, chunked prefill + paged decode through the latent pool, the two
forms of the attention, YaRN, and the step functions' contract
(one compile a program, pools donated and updated in place)."""

import collections
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import deepseek_v32 as ref  # noqa: E402

from apex_tpu.models.deepseek_v32 import (  # noqa: E402
    COUNTER_NAMES, DeepSeekV32Config, DeepSeekV32Model,
)
from apex_tpu.ops.attention_latent import mla_absorbed, mla_expanded  # noqa: E402
from apex_tpu.ops.rope import yarn_inv_freq, yarn_mscale  # noqa: E402
from apex_tpu.serving.kv_cache import (  # noqa: E402
    KVCacheConfig, PagedKVCache, init_pools,
)
from apex_tpu.serving.serve import ContinuousBatcher, Request  # noqa: E402

HF = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=4, index_head_dim=16, index_topk=8, intermediate_size=128,
    moe_intermediate_size=32, n_shared_experts=1, num_experts_per_tok=4,
    n_group=4, topk_group=2, routed_scaling_factor=2.5, rms_norm_eps=1e-6,
    rope_theta=10000.0,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"))
HELD = (1, 4, 6, 11)
PAGE, CHUNK, PAGES_PER_SEQ, SLOTS = 4, 8, 8, 4


@pytest.fixture(scope="module")
def built():
    cfg = DeepSeekV32Config.from_hf(HF, n_routed_experts=16,
                                    held_experts=HELD,
                                    params_dtype=jnp.float32)
    model = DeepSeekV32Model(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            NamedSharding(mesh, P()))
    ccfg = KVCacheConfig(
        num_layers=3, num_heads=1, head_dim=cfg.latent_dim,
        num_pages=1 + SLOTS * PAGES_PER_SEQ, page_size=PAGE, max_seqs=SLOTS,
        pages_per_seq=PAGES_PER_SEQ, dtype=jnp.float32, kind="latent",
        latent_dim=cfg.latent_dim, index_dim=cfg.index_head_dim)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=24,
                           prefill_chunk=CHUNK)
    fresh = lambda: jax.device_put(init_pools(ccfg),
                                   NamedSharding(mesh, P()))
    return model, params, ccfg, fns, fresh


@pytest.fixture(scope="module")
def decode_step(built):
    model, _, ccfg = built[:3]
    table = model.rope_table(ccfg.max_len)
    return jax.jit(lambda p, pools, tok, pos, act, pt: model.decode_step(
        p, pools, tok, pos, act, pt, page_size=PAGE, table=table))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


def _reference_logits(params, tokens, positions):
    return np.asarray(ref.forward(
        params, tokens, ref.from_hf(HF), HELD, positions=positions,
        q_block=len(tokens))[0])


# ------------------------------------------------------------ whole forward
@pytest.mark.parametrize("seed,length", [(1, 40), (2, 7), (3, 24)])
def test_forward_matches_reference(built, seed, length):
    """Lengths below, at and far above the toy ``index_topk`` 8."""
    model, params = built[:2]
    tokens = _tokens(seed, length)
    got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(tokens)))
    want = _reference_logits(params, tokens, range(length))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


# ------------------------------------------------- paged serving = reference
@pytest.mark.parametrize("length", [
    3,      # inside the first page
    5,      # across a page boundary (page 4)
    9,      # across a chunk boundary (chunk 8): the decode step selects
    17,     # third chunk; context twice the toy index_topk
    24,     # the longest prompt the steps were built for
])
def test_chunked_prefill_then_paged_decode_matches_reference(
        built, decode_step, length):
    """The first ``length - 1`` tokens through the chunk program into
    the latent pool, one paged decode step for the last: the logits of
    both programs against the reference's full forward."""
    model, params, ccfg, fns, fresh = built
    tokens = _tokens(10 + length, length)
    cache = PagedKVCache(ccfg)
    cache.admit(0, length)
    row = jnp.asarray(cache.page_table[0])
    pools = fresh()
    n = length - 1
    padded = np.zeros((-(-n // CHUNK) * CHUNK,), np.int32)
    padded[:n] = tokens[:n]
    for c0 in range(0, n, CHUNK):
        pools, _, chunk_logits = fns.chunk(
            pools, padded[c0:c0 + CHUNK], c0, n, 0, row,
            jax.random.PRNGKey(0))
    slot0 = np.arange(SLOTS) == 0
    logits, pools, _, (idx, chosen) = decode_step(
        params, pools, jnp.asarray(np.where(slot0, tokens[-1], 0)),
        jnp.asarray(np.where(slot0, n, 0)), jnp.asarray(slot0),
        jnp.asarray(cache.page_table))
    want, selections = ref.forward(
        params, tokens, ref.from_hf(HF), HELD, positions=[n - 1, n],
        q_block=length)
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(chunk_logits), want[0],
                               atol=2e-4, rtol=0)
    np.testing.assert_allclose(np.asarray(logits[0]), want[1],
                               atol=2e-4, rtol=0)
    # the decode step's selected set is the reference's, in every layer
    for layer, selected in enumerate(selections):
        mine = np.zeros(length, bool)
        mine[np.asarray(idx)[layer, 0][np.asarray(chosen)[layer, 0]]] = True
        assert (mine == np.asarray(selected)[-1]).all()
        assert mine.sum() == min(HF["index_topk"], length)


def test_batcher_serves_the_reference_greedy_tokens(built):
    """Mixed prompts and budgets through ``ContinuousBatcher`` (chunks
    interleaved with decode steps, slots reused): every token is the
    reference's argmax on the sequence so far (one reference forward a
    request, over prompt + output: it is causal)."""
    model, params, ccfg, fns, fresh = built
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
        max_prompt_len=24, chunk_fn=fns.chunk, prefill_chunk=CHUNK,
        harvest_every=3)
    shapes = [(19, 5), (5, 7), (24, 4), (11, 6), (9, 3)]
    requests = [Request(uid=i, prompt=[int(t) for t in _tokens(100 + i, n)],
                        max_new_tokens=m) for i, (n, m) in enumerate(shapes)]
    done = batcher.run(requests)
    for r in requests:
        out = done[r.uid].tokens
        assert len(out) == r.max_new_tokens
        n = len(r.prompt)
        want = _reference_logits(
            params, np.asarray(list(r.prompt) + out[:-1]),
            range(n - 1, n - 1 + len(out)))
        assert [int(t) for t in np.argmax(want, axis=-1)] == out
    # the steps' own counts ride in the carry and come with the harvest
    np.testing.assert_array_equal(batcher.step_counters,
                                  np.asarray(batcher.carry["counters"]))
    counted = dict(zip(COUNTER_NAMES, batcher.step_counters))
    assert counted["decode_steps"] == batcher.steps
    assert counted["decode_slot_layers"] == 3 * sum(m - 1 for _, m in shapes)
    assert 0 < counted["decode_choices_held"] < counted["decode_choices"]
    assert counted["decode_selected_rows"] < counted["decode_context_rows"]


def test_the_decode_program_shows_what_it_computed(built):
    """``last_logits`` / ``last_selected`` in the carry are the served
    step's own, with every slot live: each slot's logits and selected set
    after a step are the reference's on that slot's sequence so far."""
    model, params, ccfg, fns, fresh = built
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
        max_prompt_len=24, chunk_fn=fns.chunk, prefill_chunk=CHUNK,
        harvest_every=1)
    lengths = (15, 5, 16, 11)
    queue = collections.deque(
        Request(uid=i, prompt=[int(t) for t in _tokens(300 + i, n)],
                max_new_tokens=16) for i, n in enumerate(lengths))
    prompts = {r.uid: list(r.prompt) for r in queue}
    for _ in range(10):                      # 7 chunks, one a pump
        batcher.pump(queue)
    assert batcher.live_slots == SLOTS and not batcher.pending_prefill_chunks
    logits = np.asarray(batcher.carry["last_logits"])
    idx = np.asarray(batcher.carry["last_selected"])
    ok = np.asarray(batcher.carry["last_selected_valid"])
    after = np.asarray(batcher.carry["lengths"])
    for slot in range(SLOTS):
        # every token is harvested (one step a pump): a slot's context is
        # its request's prompt and all but the newest of its tokens
        uid, = [u for u, out in batcher.progress().items()
                if len(prompts[u]) + len(out) - 1 == after[slot]]
        seq = np.asarray(prompts[uid] + batcher.progress()[uid][:-1])
        want, selections = ref.forward(
            params, seq, ref.from_hf(HF), HELD, positions=[len(seq) - 1],
            q_block=len(seq))
        np.testing.assert_allclose(logits[slot], np.asarray(want)[0],
                                   atol=2e-4, rtol=0)
        for layer, selected in enumerate(selections):
            mine = np.zeros(len(seq), bool)
            mine[idx[layer, slot][ok[layer, slot]]] = True
            assert (mine == np.asarray(selected)[-1]).all()


def test_decode_step_walks_and_gathers_alike(built, monkeypatch):
    """The decode step's page walk under the selection mask against the
    same step with the chosen rows gathered through the page table and
    attended alone: the same logits on every live slot and the same
    selected sets, with slots past, at and below ``index_topk`` and one
    idle."""
    from apex_tpu.models import deepseek_v32

    model, params, ccfg, fns, fresh = built
    cache = PagedKVCache(ccfg)
    pools = fresh()
    lengths = (23, 8, 5, 0)
    for slot, n in enumerate(lengths):
        if not n:
            continue
        cache.admit(slot, n + 1)
        toks = np.zeros((-(-n // CHUNK) * CHUNK,), np.int32)
        toks[:n] = _tokens(500 + slot, n)
        for c0 in range(0, n, CHUNK):
            pools, _, _ = fns.chunk(pools, toks[c0:c0 + CHUNK], c0, n, 0,
                                    jnp.asarray(cache.page_table[slot]),
                                    jax.random.PRNGKey(0))
    table = model.rope_table(ccfg.max_len)
    args = (params, pools, jnp.asarray(_tokens(9, SLOTS)),
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray([n > 0 for n in lengths]),
            jnp.asarray(cache.page_table))

    def gathered(q_nope, q_rope, pool, layer, page_table, lengths, w_uk,
                 w_uv, scale, *, selected):
        # the marked positions, lower first, and the rows they name
        marked, idx = jax.lax.top_k(selected.astype(jnp.int32),
                                    HF["index_topk"])
        rows = pool[layer, jnp.take_along_axis(page_table, idx // PAGE,
                                               axis=1), idx % PAGE]
        return mla_absorbed(q_nope, q_rope, rows, marked > 0, w_uk, w_uv,
                            scale)

    out = []
    for attend in (deepseek_v32.mla_paged, gathered):
        monkeypatch.setattr(deepseek_v32, "mla_paged", attend)
        step = jax.jit(lambda *a: model.decode_step(
            *a, page_size=PAGE, table=table))
        logits, _, stats, (idx, chosen) = step(*args)
        out.append([np.asarray(v) for v in (logits, stats, idx, chosen)])
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(out[0][0][live], out[1][0][live],
                               atol=2e-4, rtol=0)
    for walked, gathered_ in zip(out[0][1:], out[1][1:]):
        np.testing.assert_array_equal(walked, gathered_)


def test_monolithic_prefill_signature_runs_the_chunks(built):
    model, params, ccfg, fns, fresh = built
    tokens = _tokens(7, 13)
    cache = PagedKVCache(ccfg)
    cache.admit(0, 20)
    padded = np.zeros((1, 24), np.int32)
    padded[0, :13] = tokens
    _, first = fns.prefill(fresh(), jnp.asarray(padded), jnp.int32(13),
                           jnp.asarray(cache.page_table[0]),
                           jax.random.PRNGKey(0))
    want = _reference_logits(params, tokens, [12])
    assert int(first) == int(np.argmax(want[0]))


# --------------------------------------------------------- the steps' contract
def test_each_program_compiles_once(built):
    """One executable for the decode step and one per context extent of
    the chunk step (8, 16, 24), however many requests come and go."""
    model, params, ccfg, fns, fresh = built
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
        max_prompt_len=24, chunk_fn=fns.chunk, prefill_chunk=CHUNK)
    mk = lambda i, n, m: Request(
        uid=i, prompt=[int(t) for t in _tokens(200 + i, n)], max_new_tokens=m)
    batcher.run([mk(0, 24, 3), mk(1, 5, 4), mk(2, 12, 2)])
    sizes = (fns.decode_jit._cache_size(), fns.chunk_jit._cache_size())
    batcher.run([mk(3, 23, 5), mk(4, 3, 2), mk(5, 17, 6), mk(6, 9, 3),
                 mk(7, 20, 2)])
    assert (fns.decode_jit._cache_size(),
            fns.chunk_jit._cache_size()) == sizes
    assert sizes[0] == 1 and sizes[1] <= 3


def test_steps_update_the_donated_pools_in_place(built):
    """The compiled decode and chunk programs alias every pool buffer to
    its output (no second pool, no per-layer copy), and a call consumes
    the pools it was given."""
    model, params, ccfg, fns, fresh = built
    pools = fresh()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pools.values())
    from apex_tpu.serving.serve import init_carry

    carry = init_carry(SLOTS, sharding=fns.carry_sharding,
                       extras=fns.decode.carry_extras)
    table = jnp.zeros((SLOTS, PAGES_PER_SEQ), jnp.int32)
    compiled = fns.decode_jit.lower(params, pools, carry, table).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    compiled = fns.chunk_jit.lower(
        params, pools, jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(0),
        jnp.int32(5), jnp.int32(0), table[0], jax.random.PRNGKey(0),
        ctx_len=CHUNK).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    out, _ = fns.decode(pools, carry, table)
    assert all(a.is_deleted() for a in pools.values())
    assert {k: v.shape for k, v in out.items()} == {
        k: v.shape for k, v in fresh().items()}


def test_scopes_reach_the_compiled_text(built):
    model, params, ccfg, fns, fresh = built
    from apex_tpu.serving.serve import init_carry

    text = fns.decode_jit.lower(
        params, fresh(), init_carry(SLOTS, sharding=fns.carry_sharding,
                                    extras=fns.decode.carry_extras),
        jnp.zeros((SLOTS, PAGES_PER_SEQ), jnp.int32)).compile().as_text()
    for scope in ("tlm.decode", "tlm.attn.mla", "tlm.attn.mla.core",
                  "tlm.attn.index", "tlm.attn.index.core", "tlm.attn.select",
                  "tlm.moe.route", "tlm.moe.experts", "tlm.moe.shared"):
        assert scope + "/" in text, scope


def test_cache_config_mismatch_is_refused(built):
    model, params, ccfg, fns, fresh = built
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    kv = KVCacheConfig(num_layers=3, num_heads=4, head_dim=16, num_pages=9)
    with pytest.raises(ValueError, match="latent row"):
        model.decode_fns(params, mesh, kv, max_prompt_len=24,
                         prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="multiple of the page size"):
        model.decode_fns(params, mesh, ccfg, max_prompt_len=24,
                         prefill_chunk=6)


# -------------------------------------------------- two forms of one attention
@pytest.mark.parametrize("seed", [0, 1])
def test_absorbed_form_equals_expanded_form(seed):
    B, H, dn, dr, dv, dc, S, K = 3, 4, 16, 8, 12, 20, 40, 9
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q_nope = jax.random.normal(ks[0], (B, H, dn))
    q_rope = jax.random.normal(ks[1], (B, H, dr))
    rows = jax.random.normal(ks[2], (S, dc + dr))
    rows = jnp.pad(rows, ((0, 0), (0, 4)))          # the pool's lane padding
    w_uk = jax.random.normal(ks[3], (dc, H, dn)) * dc ** -0.5
    w_uv = jax.random.normal(ks[4], (dc, H, dv)) * dc ** -0.5
    idx = jnp.stack([jax.random.permutation(k, S)[:K]
                     for k in jax.random.split(ks[5], B)])
    chosen = jnp.arange(K)[None] < jnp.asarray([[K], [K - 3], [1]])
    mask = jnp.zeros((B, S), bool).at[
        jnp.arange(B)[:, None], idx].set(chosen)
    expanded = mla_expanded(q_nope, q_rope, rows, w_uk, w_uv, mask, 0.3,
                            head_block=2)
    absorbed = mla_absorbed(q_nope, q_rope, rows[idx], chosen, w_uk, w_uv,
                            0.3)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5, rtol=0)


# ------------------------------------------------------------------ YaRN
def test_yarn_frequencies_against_hand_computed_values():
    """The published settings: head dim 64, theta 10000, factor 40,
    beta 32 / 1 over 4096 positions.  Correction dimensions 64 ln(4096 /
    (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10 and 64 ln(4096 / (2 pi)) /
    (2 ln 10000) = 22.51 -> 23."""
    f = yarn_inv_freq(64, base=10000.0, factor=40, beta_fast=32, beta_slow=1,
                      original_max_position=4096)
    theta = lambda i: 10000.0 ** (-2 * i / 64)
    assert f.shape == (32,)
    assert f[0] == 1.0
    assert f[10] == pytest.approx(theta(10), rel=1e-12)     # ramp 0
    assert f[23] == pytest.approx(theta(23) / 40, rel=1e-12)  # ramp 1
    assert f[31] == pytest.approx(theta(31) / 40, rel=1e-12)
    # i = 16: theta 0.01, ramp 6/13
    assert f[16] == pytest.approx(0.01 * (7 / 13) + 0.00025 * (6 / 13),
                                  rel=1e-12)
    np.testing.assert_allclose(f, ref.yarn_inv_freq(dict(
        qk_rope_head_dim=64, rope_theta=10000, rope_scaling=dict(
            factor=40, beta_fast=32, beta_slow=1,
            original_max_position_embeddings=4096))), rtol=1e-12)


def test_yarn_softmax_scale_against_hand_computed_value():
    m = 0.1 * math.log(40) + 1
    assert yarn_mscale(40, 1.0) == pytest.approx(1.3688879454, rel=1e-9)
    assert yarn_mscale(1.0) == 1.0
    published = dict(HF, qk_nope_head_dim=128, qk_rope_head_dim=64,
                     rope_scaling=dict(HF["rope_scaling"], factor=40))
    cfg = DeepSeekV32Config.from_hf(published, n_routed_experts=16,
                                    held_experts=HELD)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert cfg.softmax_scale == pytest.approx(0.135234, rel=1e-5)
    assert ref.softmax_scale(published) == pytest.approx(cfg.softmax_scale)
