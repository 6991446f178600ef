"""Arcee ``afmoe`` (Trinity) on the serving path, at a small size on the
CPU in float32, against the benchmark's plain reference
(``benchmarks/reference/afmoe.py``, imported, nothing of the program in
it): the whole forward; chunked prefill then paged decode through
``ContinuousBatcher`` and the two page classes, with a context that
wraps the window class's ring more than twice; the expert shares adding
up; which layers feel a shift of all positions; the step functions'
contract."""

import collections
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

from reference import afmoe as ref  # noqa: E402

from apex_tpu.models.afmoe import (  # noqa: E402
    COUNTER_NAMES, FULL, SLIDING, AfmoeConfig, AfmoeModel,
)
from apex_tpu.serving.kv_cache import (  # noqa: E402
    KVCacheConfig, PagedKVCache, init_pools,
)
from apex_tpu.serving.serve import ContinuousBatcher, Request  # noqa: E402
from apex_tpu.transformer.moe import HeldExpertsMLP  # noqa: E402

HF = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, num_experts_per_tok=2,
    num_shared_experts=1, route_scale=2.448, rms_norm_eps=1e-5,
    rope_theta=10000.0, mup_enabled=True, sliding_window=8,
    layer_types=[SLIDING, SLIDING, SLIDING, SLIDING, FULL])
EXPERTS, HELD = 16, (1, 4, 6, 11)
PAGE, CHUNK, PAGES_PER_SEQ, SLOTS = 4, 8, 16, 3
RING = (8 + CHUNK) // PAGE + 1          # window + chunk + one page = 5


def _perturbed(params, key):
    """Norm gains away from 1 (the seeded ones are exactly 1, which a
    misplaced or missing gain would pass)."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.2 * jax.random.normal(k, leaf.shape, leaf.dtype)
        if leaf.ndim == 1 and leaf.shape[0] != EXPERTS else leaf
        for leaf, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def built():
    cfg = AfmoeConfig.from_hf(HF, num_experts=EXPERTS, held_experts=HELD,
                              params_dtype=jnp.float32)
    model = AfmoeModel(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    params = jax.device_put(
        _perturbed(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(9)),
        NamedSharding(mesh, P()))
    ccfg = KVCacheConfig.of_classes(
        model.cache_classes(slots=SLOTS, pages_per_seq=PAGES_PER_SEQ,
                            page_size=PAGE, prefill_chunk=CHUNK),
        page_size=PAGE, max_seqs=SLOTS, dtype=jnp.float32)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=40,
                           prefill_chunk=CHUNK)
    fresh = lambda: jax.device_put(init_pools(ccfg),
                                   NamedSharding(mesh, P()))
    return model, params, ccfg, fns, fresh


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


def _reference(params, tokens, positions, held=HELD, **kwargs):
    logits, attn = ref.forward(
        params, tokens, ref.from_hf(HF), held, positions=positions,
        q_block=len(tokens), **kwargs)
    return np.asarray(logits), np.asarray(attn)


def test_classes_of_the_cache(built):
    model, _, ccfg = built[:3]
    full, window = ccfg.page_classes
    assert (full.name, full.layers, full.window) == ("full", (4,), 0)
    assert (window.name, window.layers, window.window,
            window.pages_per_seq) == ("window", (0, 1, 2, 3), 8, RING)
    assert ccfg.table_columns == ((0, 16), (16, 16 + RING))
    pools = jax.eval_shape(lambda: init_pools(ccfg))
    assert pools["full.k"].shape == (1, 1 + SLOTS * 16, 2, PAGE, 16)
    assert pools["window.v"].shape == (4, 1 + SLOTS * RING, 2, PAGE, 16)


# ------------------------------------------------------------ whole forward
@pytest.mark.parametrize("seed,length", [(1, 40), (2, 7), (3, 24)])
def test_forward_matches_reference(built, seed, length):
    """Lengths below and far above the toy window 8."""
    model, params = built[:2]
    tokens = _tokens(seed, length)
    got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(tokens)))
    want, _ = _reference(params, tokens, range(length))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_only_window_layers_feel_a_shift_of_all_positions(built):
    """A full layer has no position signal: with every layer made full
    the logits do not move when all positions are shifted; a window
    layer rotates by its position differences only (RoPE is relative),
    so the model as published does not move either, but moving ONE
    token's position moves it — and leaves the all-full model alone."""
    model, params = built[:2]
    tokens = jnp.asarray(_tokens(4, 20))
    base = jnp.arange(20, dtype=jnp.int32)
    all_full = AfmoeModel(AfmoeConfig.from_hf(
        dict(HF, layer_types=[FULL] * 5), num_experts=EXPERTS,
        held_experts=HELD, params_dtype=jnp.float32))
    for m, feels in ((all_full, False), (model, True)):
        run = jax.jit(m.apply)
        at_0 = np.asarray(run(params, tokens, base))
        np.testing.assert_allclose(
            np.asarray(run(params, tokens, base + 7)), at_0, atol=2e-4)
        moved = np.asarray(run(params, tokens, base.at[12].add(3)))
        assert (np.abs(moved - at_0).max() > 1e-2) == feels
    # and the reference agrees on what a position is
    want, _ = _reference(params, np.asarray(tokens), range(20),
                         token_positions=np.asarray(base.at[12].add(3)))
    np.testing.assert_allclose(
        np.asarray(jax.jit(model.apply)(params, tokens, base.at[12].add(3))),
        want, atol=2e-4, rtol=0)


# ---------------------------------------------------- the shares add up
def test_shares_over_a_partition_add_up_to_the_uncut_layer(built):
    """``tests/test_held_experts.py``'s test at this model's shape
    (n_group 1, top-2 of 16, width 32 on hidden 64): the shares of four
    chips, the shared expert counted once, give the whole layer."""
    model = built[0]
    moe = model.moe
    whole = moe.init(jax.random.PRNGKey(3), EXPERTS)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, 64), jnp.float32)
    every = tuple(range(EXPERTS))
    uncut, _ = moe.apply(whole, x, every)
    sh = whole["shared"]
    shared = HeldExpertsMLP._swiglu(x, sh["w_gate"], sh["w_up"],
                                    sh["w_down"])
    total = shared
    for part in (every[0:4], every[4:8], every[8:12], every[12:16]):
        cut = dict(whole, experts=jax.tree.map(
            lambda w: w[jnp.asarray(part)], whole["experts"]))
        total = total + moe.apply(cut, x, part)[0] - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=0)
    # and the reference's share is the program's
    ref_y = ref.moe(x, dict(whole, experts=jax.tree.map(
        lambda w: w[jnp.asarray(HELD)], whole["experts"])),
        ref.from_hf(HF), HELD)
    cut = dict(whole, experts=jax.tree.map(
        lambda w: w[jnp.asarray(HELD)], whole["experts"]))
    np.testing.assert_allclose(np.asarray(moe.apply(cut, x, HELD)[0]),
                               np.asarray(ref_y), atol=2e-5, rtol=0)


# ------------------------------------------------- paged serving = reference
def _serve(built, requests, **kwargs):
    """The requests through ``ContinuousBatcher``, one decode step a
    pump; returns (completions, per uid the positions its decode steps
    were read at with their logits and attention outputs, the chunk
    program's logits at each prompt's last position, the batcher)."""
    model, params, ccfg, fns, fresh = built
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
        max_prompt_len=40, chunk_fn=fns.chunk, prefill_chunk=CHUNK,
        harvest_every=1, **kwargs)
    queue = collections.deque(requests)
    seen = {r.uid: {"at": [], "logits": [], "attn": []} for r in requests}
    chunk_logits = {}
    while batcher.pump(queue):
        carry = jax.device_get(batcher.carry)
        for slot, m in batcher._meta.items():
            uid, s = m["req"].uid, seen[m["req"].uid]
            if uid not in chunk_logits:
                chunk_logits[uid] = np.asarray(batcher.last_prefill_logits)
            at = int(carry["lengths"][slot]) - 1
            if at >= len(m["req"].prompt) and (
                    not s["at"] or at > s["at"][-1]):
                s["at"].append(at)
                s["logits"].append(carry["last_logits"][slot])
                s["attn"].append(carry["last_attn"][:, slot])
    return batcher.completions, seen, chunk_logits, batcher


def test_batcher_prefill_chunks_then_decode_matches_reference(built):
    """A prompt of 2.5 chunks and 3 x window = 24 checked decode steps beside a
    short request: every decode position's logits and attention outputs
    (last window layer, full layer), and the chunk program's logits of
    the prompt's last position, against the reference's full forward on
    prompt + generated tokens.  40 tokens of context wrap the 5-page
    ring (20 tokens) twice."""
    params = built[1]
    long, short = _tokens(21, 19), _tokens(22, 5)
    done, seen, chunk_logits, batcher = _serve(built, [
        Request(uid="long", prompt=list(long), max_new_tokens=26),
        Request(uid="short", prompt=list(short), max_new_tokens=9)])
    for uid, prompt in (("long", long), ("short", short)):
        n, new = len(prompt), done[uid].tokens
        sequence = np.concatenate([prompt, new[:-1]]).astype(np.int32)
        want, attn = _reference(params, sequence, range(n - 1, len(sequence)))
        np.testing.assert_allclose(chunk_logits[uid], want[0], atol=3e-4,
                                   rtol=0)
        s = seen[uid]
        # every position but the last (its slot retires inside the pump)
        steps = len(sequence) - n - 1
        assert s["at"] == list(range(n, n + steps))
        np.testing.assert_allclose(np.stack(s["logits"]), want[1:-1],
                                   atol=3e-4, rtol=0)
        got_attn = np.stack(s["attn"])                    # (steps, 2, Hq*d)
        np.testing.assert_allclose(got_attn[:, 0], attn[3, 1:-1], atol=3e-4)
        np.testing.assert_allclose(got_attn[:, 1], attn[4, 1:-1], atol=3e-4)
        # greedy: the served tokens are the reference's argmax
        assert list(new) == list(np.argmax(want, -1))
    assert len(seen["long"]["at"]) == 24 and 19 + 24 > 2 * RING * PAGE
    assert batcher.cache.overwritten_pages == {"window": 11 - RING}
    assert batcher.cache.pages_in_use() == {"full": 0, "window": 0}


def test_sampled_streams_do_not_depend_on_the_slot(built):
    model, params, ccfg, _, fresh = built
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=40,
                           prefill_chunk=CHUNK, temperature=0.8, top_k=20)
    prompt = list(_tokens(30, 11))

    def run(extra):
        b = ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
            max_prompt_len=40, chunk_fn=fns.chunk, prefill_chunk=CHUNK)
        reqs = [Request(uid=i, prompt=list(_tokens(40 + i, 6)),
                        max_new_tokens=4, seed=i) for i in range(extra)]
        reqs.append(Request(uid="it", prompt=prompt, max_new_tokens=12,
                            seed=77))
        return b.run(reqs)["it"].tokens

    assert run(0) == run(2)


def test_step_contract(built):
    """One executable a program (and one a context bucket for the
    chunk), counters in ``COUNTER_NAMES``' order, window rows bounded by
    window + page - 1 a slot and layer."""
    model, params, ccfg, fns, _ = built
    assert fns.chunk.ctx_buckets == (8, 16, 32, 40)
    done, seen, _, batcher = _serve(built, [
        Request(uid=i, prompt=list(_tokens(50 + i, 9 + 7 * i)),
                max_new_tokens=12) for i in range(3)])
    assert fns.decode_jit._cache_size() == 1
    c = dict(zip(COUNTER_NAMES, batcher.step_counters))
    steps = c["decode_steps"]
    assert steps > 0 and c["decode_slot_layers"] <= steps * SLOTS * 5
    live = c["decode_slot_layers"] / 5
    assert c["decode_window_rows"] <= live * 4 * (8 + PAGE - 1)
    assert c["decode_full_rows"] == c["decode_context_rows"]   # one layer
    assert c["decode_window_rows"] < 4 * c["decode_context_rows"]
    assert c["decode_choices"] == live * 4 * 2
    assert 0 < c["decode_choices_held"] < c["decode_choices"]


def test_wrong_cache_and_prefix_cache_are_refused(built):
    model, params, ccfg, fns, fresh = built
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    flat = KVCacheConfig(num_layers=5, num_heads=2, head_dim=16,
                         num_pages=9, page_size=PAGE, max_seqs=2,
                         pages_per_seq=4, dtype=jnp.float32)
    with pytest.raises(ValueError, match="page classes"):
        model.decode_fns(params, mesh, flat, max_prompt_len=8,
                         prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="window class"):
        ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
            max_prompt_len=40, chunk_fn=fns.chunk, prefill_chunk=CHUNK,
            prefix_cache=True)
