"""Falcon-H1 on the serving path, at a small size on the CPU in float32,
against the benchmark's plain reference
(``benchmarks/reference/falcon_h1.py``, imported, nothing of the program
in it; its state-space recurrence runs token by token): the whole
forward; the chunked SSD scan and the decode state update against the
recurrence; chunked prefill then paged decode through
``ContinuousBatcher`` with a three-chunk prompt interleaved with other
slots' decode steps, a padded last chunk and a slot retired and
admitted again; the two mixer branches against the reference's layer;
the reference itself against the published ``transformers`` code; the
cache's per-slot state kind and what it refuses."""

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

from reference import falcon_h1 as ref  # noqa: E402

from apex_tpu.models import falcon_h1  # noqa: E402
from apex_tpu.models.falcon_h1 import (  # noqa: E402
    CONV, COUNTER_NAMES, STATE, FalconH1Config, FalconH1Model,
)
from apex_tpu.ops.ssm import ssd_chunk_scan, ssm_state_update  # noqa: E402
from apex_tpu.serving.kv_cache import (  # noqa: E402
    KVCacheConfig, PagedKVCache, SlotState, init_pools,
)
from apex_tpu.serving.serve import (  # noqa: E402
    ContinuousBatcher, HandoffPacket, Request,
)

#: the published ratios at toy widths: 2 groups, heads divisible by the
#: groups, state width (16) above head_dim (8), every multiplier as
#: published
HF = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=4,
    rms_norm_eps=1e-5, rope_theta=1e11,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284])
PAGE, CHUNK, PAGES_PER_SEQ, SLOTS, MAX_PROMPT = 4, 8, 12, 3, 40


def _perturbed(params, key):
    """Norm gains and the SSM's per-head vectors away from their seeded
    values (gains of exactly 1 would pass a misplaced or missing one)."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.2 * jax.random.normal(k, leaf.shape, leaf.dtype)
        if leaf.ndim == 1 else leaf for leaf, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def built():
    model = FalconH1Model(FalconH1Config.from_hf(HF, params_dtype=jnp.float32))
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    params = jax.device_put(
        _perturbed(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(9)),
        NamedSharding(mesh, P()))
    ccfg = model.cache_config(slots=SLOTS, pages_per_seq=PAGES_PER_SEQ,
                              page_size=PAGE, dtype=jnp.float32)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=MAX_PROMPT,
                           prefill_chunk=CHUNK)
    fresh = lambda: jax.device_put(init_pools(ccfg), NamedSharding(mesh, P()))
    return model, params, ccfg, fns, fresh


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


def _reference(params, tokens, **kwargs):
    logits, final, kept = ref.forward(params, tokens, ref.from_hf(HF),
                                      **kwargs)
    return np.asarray(logits), np.asarray(final), np.asarray(kept)


# ------------------------------------------------------------ whole forward
@pytest.mark.parametrize("seed,length", [(1, 37), (2, 5), (3, 24)])
def test_forward_matches_reference(built, seed, length):
    model, params = built[:2]
    tokens = _tokens(seed, length)
    got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(tokens)))
    want = _reference(params, tokens)[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_mixer_branches_add_what_the_reference_layer_adds(built):
    """Per layer, what attention and the state-space branch add to the
    residual stream, each and summed, against the reference's layer on
    the same input; and the state-space branch is no small part of it."""
    model, params = built[:2]
    tokens = _tokens(5, 29)
    _, kept = jax.jit(lambda p, t: model.apply(p, t, branches=True))(
        params, jnp.asarray(tokens))
    cfg = ref.from_hf(HF)
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["weight"][tokens] * cfg["embedding_multiplier"]
        where = jnp.arange(len(tokens))
        for layer, w in enumerate(params["layers"]):
            x_next, (attn, ssm), _, _ = ref.layer(x, w, where, cfg)
            got_a, got_s = (np.asarray(t) for t in kept[layer])
            np.testing.assert_allclose(got_a, attn, atol=2e-5, rtol=0)
            np.testing.assert_allclose(got_s, ssm, atol=2e-5, rtol=0)
            np.testing.assert_allclose(got_a + got_s, attn + ssm, atol=3e-5,
                                       rtol=0)
            assert np.abs(ssm).mean() > 0.3 * np.abs(attn).mean()
            x = x_next


# ----------------------------------------------------- the two operations
def _ssm_inputs(seed, T, H=4, P_=8, N=16, G=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (T, H, P_))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    B = jax.random.normal(ks[3], (T, G, N))
    C = jax.random.normal(ks[4], (T, G, N))
    D = jax.random.normal(ks[5], (H,))
    s0 = jax.random.normal(ks[6], (H, P_, N))
    return x, dt, A, B, C, D, s0


def _recurrence(x, dt, A, B, C, D, s0):
    R = x.shape[1] // B.shape[1]
    by_head = lambda t: jnp.repeat(t, R, axis=1)
    return ref.recurrence(x, dt, A, by_head(B), by_head(C), D, s0, 0)[:2]


@pytest.mark.parametrize("T,start_from_zero", [
    (200, True), (200, False), (77, False), (300, True), (131, False)])
def test_chunked_scan_matches_the_recurrence(T, start_from_zero):
    """Blocks of 128 (the published ``mamba_chunk_size``) at lengths that
    are not multiples of it, from zero state and from a given one; the
    tail's tokens with ``dt = 0`` leave the final state where the last
    real token left it."""
    x, dt, A, B, C, D, s0 = _ssm_inputs(T, T)
    if start_from_zero:
        s0 = jnp.zeros_like(s0)
    with jax.default_matmul_precision("highest"):
        y, final = jax.jit(ssd_chunk_scan)(x, dt, A, B, C, D, s0)
        want_y, want_final = _recurrence(x, dt, A, B, C, D, s0)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               atol=2e-5 * scale, rtol=0)
    np.testing.assert_allclose(np.asarray(final), np.asarray(want_final),
                               atol=2e-5 * float(jnp.max(jnp.abs(want_final))),
                               rtol=0)
    real = T - 13
    padded_dt = dt.at[real:].set(0.0)
    _, cut = jax.jit(ssd_chunk_scan)(x, padded_dt, A, B, C, D, s0)
    _, want_cut = _recurrence(x[:real], dt[:real], A, B[:real], C[:real], D,
                              s0)
    np.testing.assert_allclose(np.asarray(cut), np.asarray(want_cut),
                               atol=2e-5 * float(jnp.max(jnp.abs(want_cut))),
                               rtol=0)


@pytest.mark.parametrize("implementation,dtype", [
    ("xla", jnp.float32), ("pallas", jnp.float32), ("pallas", jnp.bfloat16)])
def test_state_update_is_one_step_of_the_recurrence(implementation, dtype):
    """Every live slot's state advanced by one step of the recurrence
    (and ``y`` its output), in place in the stacked pool; every other
    slot, and every other layer, bit for bit as it was."""
    S, L, layer = 5, 3, 1
    x, dt, A, B, C, D, _ = _ssm_inputs(11, S)
    pool = jax.random.normal(jax.random.PRNGKey(12),
                             (L, S, 4, 8, 16)).astype(dtype)
    live = jnp.asarray([True, False, True, True, False])
    y, out = jax.jit(lambda p, *a: ssm_state_update(
        p, layer, *a, implementation=implementation))(
        pool, x, dt, A, B, C, D, live)
    for s in range(S):
        if not live[s]:
            np.testing.assert_array_equal(np.asarray(out[layer, s]),
                                          np.asarray(pool[layer, s]))
            continue
        one = lambda t: t[s:s + 1]
        want_y, want = _recurrence(one(x), one(dt), A, one(B), one(C), D,
                                   pool[layer, s].astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(out[layer, s], np.float32),
            np.asarray(want.astype(dtype), np.float32),
            atol=1e-5 if dtype == jnp.float32 else 2e-2, rtol=0)
        np.testing.assert_allclose(np.asarray(y[s]), np.asarray(want_y[0]),
                                   atol=1e-4, rtol=0)
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(out[other]),
                                      np.asarray(pool[other]))


# ------------------------------------------------- paged serving = reference
def _serve(built, requests):
    """The requests through ``ContinuousBatcher``, one decode step a
    pump.  Returns (completions, per uid the positions its decode steps
    were read at with their logits, per uid every chunk's (start, plen,
    logits), the slot each uid was served in, the batcher)."""
    model, params, ccfg, fns, fresh = built
    chunks = collections.defaultdict(list)
    box = {}

    def chunk(pools, toks, start, plen, write_from, row, key, *, slot):
        pools, tok, logits = fns.chunk(pools, toks, start, plen, write_from,
                                       row, key, slot=slot)
        uid = box["b"]._prefilling[slot]["req"].uid
        chunks[uid].append((int(start), int(plen), np.asarray(logits)))
        return pools, tok, logits

    chunk.prefill_chunk = CHUNK
    batcher = box["b"] = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
        max_prompt_len=MAX_PROMPT, chunk_fn=chunk, prefill_chunk=CHUNK,
        harvest_every=1)
    queue = collections.deque(requests)
    seen = {r.uid: {"at": [], "logits": []} for r in requests}
    slot_of = {}
    while batcher.pump(queue):
        carry = jax.device_get(batcher.carry)
        for slot, m in batcher._meta.items():
            uid, s = m["req"].uid, seen[m["req"].uid]
            slot_of[uid] = slot
            at = int(carry["lengths"][slot]) - 1
            if at >= len(m["req"].prompt) and (
                    not s["at"] or at > s["at"][-1]):
                s["at"].append(at)
                s["logits"].append(carry["last_logits"][slot])
    return batcher.completions, seen, chunks, slot_of, batcher


def test_batcher_chunks_then_decode_match_the_reference(built):
    """``long`` (20 tokens: three chunks, the last padded) goes in after
    ``short`` has started decoding, so decode steps of the other slots
    run between its chunks; ``late`` waits for a slot and is admitted
    into one that served another request (its state must start from
    zero).  Every chunk's logits and every decode step's, against the
    reference's full forward on each request's own sequence; the final
    state of each slot against the reference's state there."""
    model, params, ccfg = built[:3]
    reqs = [Request(uid="short", prompt=list(_tokens(20, 5)),
                    max_new_tokens=14),
            Request(uid="long", prompt=list(_tokens(21, 20)),
                    max_new_tokens=7),
            Request(uid="mid", prompt=list(_tokens(22, 9)),
                    max_new_tokens=3),
            Request(uid="late", prompt=list(_tokens(23, 11)),
                    max_new_tokens=6)]
    done, seen, chunks, slot_of, batcher = _serve(built, reqs)
    assert [s for s, _, _ in chunks["long"]] == [0, 8, 16]
    assert slot_of["late"] in (slot_of["mid"], slot_of["short"])
    for r in reqs:
        toks = done[r.uid].tokens
        assert len(toks) == r.max_new_tokens
        seq = np.asarray(list(r.prompt) + toks[:-1], np.int32)
        want, finals, _ = _reference(params, seq)
        for start, plen, logits in chunks[r.uid]:
            at = min(plen, start + CHUNK) - 1
            np.testing.assert_allclose(logits, want[at], atol=2e-4, rtol=0)
        # the step a slot finishes on retires it in the same pump: read
        # are all but that one (the final state below covers it)
        at = seen[r.uid]["at"]
        assert at == list(range(len(r.prompt), len(r.prompt) + len(at)))
        assert len(at) >= len(seq) - len(r.prompt) - 1
        np.testing.assert_allclose(np.stack(seen[r.uid]["logits"]),
                                   want[at], atol=2e-4, rtol=0)
        assert toks[0] == int(np.argmax(want[len(r.prompt) - 1]))
    # the slot ``late`` finished in holds the state after its sequence
    seq = np.asarray(reqs[3].prompt + done["late"].tokens[:-1], np.int32)
    _, finals, _ = _reference(params, seq)
    state = np.asarray(batcher.pools[STATE][:, slot_of["late"]])
    np.testing.assert_allclose(state, finals,
                               atol=1e-4 * np.abs(finals).max(), rtol=0)
    counters = dict(zip(COUNTER_NAMES, batcher.step_counters))
    per_slot = 2 * 2 * 4 * 8 * 16 * 4
    assert counters["ssm_state_bytes"] == per_slot * counters[
        "decode_slot_layers"] / 2


def test_prefill_span_says_where_the_state_came_from(built, monkeypatch):
    from apex_tpu.serving import serve

    said = []
    real = serve.host_span

    def spy(name, **stats):
        span = real(name, **stats)
        if name == "serve.dispatch_prefill":
            original = span.set_metadata
            span.set_metadata = lambda **kw: (said.append(kw), original(**kw))
        return span

    monkeypatch.setattr(serve, "host_span", spy)
    _serve(built, [Request(uid="p", prompt=list(_tokens(30, 19)),
                           max_new_tokens=2)])
    assert [k["ssm_state_in"] for k in said if "ssm_state_in" in k] == [
        "zero", "carried", "carried"]


# ------------------------------------------- the published code, pinned
def test_reference_matches_the_published_transformers_code():
    """``benchmarks/reference/falcon_h1.py`` against ``transformers``'
    ``FalconH1ForCausalLM`` (its ``torch_forward`` path on the CPU) at a
    toy size, the same seeded weights in both: every multiplier, the muP
    split of the input projection, the grouped gated norm, the
    convolution's bias, RoPE at the published theta."""
    torch = pytest.importorskip("torch")
    try:
        from transformers import FalconH1Config as HFConfig
        from transformers import FalconH1ForCausalLM
    except ImportError as e:
        pytest.skip(f"transformers has no falcon_h1: {e}")
    hf_cfg = HFConfig(
        **{k: v for k, v in HF.items() if k != "mamba_chunk_size"},
        mamba_chunk_size=8, mamba_expand=2, mamba_conv_bias=True,
        mamba_proj_bias=False, mamba_norm_before_gate=False,
        mamba_rms_norm=True, projectors_bias=False, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False)
    hf_cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    hf = FalconH1ForCausalLM(hf_cfg).float().eval()
    model = FalconH1Model(FalconH1Config.from_hf(HF, params_dtype=jnp.float32))
    params = jax.tree.map(np.asarray, _perturbed(
        model.init(jax.random.PRNGKey(4)), jax.random.PRNGKey(5)))
    t = lambda a: torch.from_numpy(np.ascontiguousarray(a))
    sd = {"model.embed_tokens.weight": t(params["embedding"]["weight"]),
          "lm_head.weight": t(params["head"]["weight"].T),
          "model.final_layernorm.weight": t(params["final_norm"]["weight"])}
    for i, w in enumerate(params["layers"]):
        pre = f"model.layers.{i}."
        a, s, m = w["attn"], w["ssm"], w["mlp"]
        sd.update({
            pre + "input_layernorm.weight": t(w["norm_in"]),
            pre + "pre_ff_layernorm.weight": t(w["norm_mlp"]),
            **{pre + f"self_attn.{n}_proj.weight": t(a["w" + n].T)
               for n in ("q", "k", "v", "o")},
            pre + "mamba.in_proj.weight": t(s["in_proj"].T),
            pre + "mamba.conv1d.weight": t(s["conv_w"].T[:, None, :]),
            pre + "mamba.conv1d.bias": t(s["conv_b"]),
            pre + "mamba.dt_bias": t(s["dt_bias"]),
            pre + "mamba.A_log": t(s["A_log"]),
            pre + "mamba.D": t(s["D"]),
            pre + "mamba.norm.weight": t(s["norm"]),
            pre + "mamba.out_proj.weight": t(s["out_proj"].T),
            pre + "feed_forward.gate_proj.weight": t(m["w_gate"].T),
            pre + "feed_forward.up_proj.weight": t(m["w_up"].T),
            pre + "feed_forward.down_proj.weight": t(m["w_down"].T)})
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    assert not unexpected and not [k for k in missing if "mup" not in k]
    tokens = _tokens(40, 23)
    with torch.no_grad():
        want = hf(input_ids=t(tokens.astype(np.int64))[None],
                  use_cache=False).logits[0].numpy()
    got = _reference(params, tokens)[0]
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=5e-4 * np.abs(want).max(),
                               rtol=0)


# --------------------------------------------------- the cache's state kind
def test_init_pools_builds_the_state_pools_beside_the_pages(built,
                                                           monkeypatch):
    ccfg = built[2]
    pools = jax.eval_shape(lambda: init_pools(ccfg))
    assert pools[STATE].shape == (2, SLOTS, 4, 8, 16)
    assert pools[STATE].dtype == jnp.float32
    assert pools[CONV].shape == (2, SLOTS, 3, 32 + 2 * 2 * 16)
    assert pools["k"].shape == (2, 1 + SLOTS * PAGES_PER_SEQ, 2, PAGE, 16)
    assert ccfg.has_state
    monkeypatch.setattr(falcon_h1, "STATE_DTYPE", jnp.bfloat16)
    bf16 = FalconH1Model(FalconH1Config.from_hf(HF)).cache_config(
        slots=SLOTS, pages_per_seq=PAGES_PER_SEQ, page_size=PAGE)
    assert jax.eval_shape(lambda: init_pools(bf16))[STATE].dtype \
        == jnp.bfloat16
    with pytest.raises(ValueError, match="not built"):
        FalconH1Config.from_hf(dict(HF, mamba_norm_before_gate=True))


def test_compat_key_sees_the_state(built):
    ccfg = built[2]
    plain = KVCacheConfig(num_layers=2, num_heads=2, head_dim=16,
                          num_pages=ccfg.num_pages, page_size=PAGE,
                          max_seqs=SLOTS, pages_per_seq=PAGES_PER_SEQ,
                          dtype=jnp.float32)
    assert PagedKVCache(plain).compat_key() != PagedKVCache(ccfg).compat_key()
    bf16 = SlotState(STATE, 2, (4, 8, 16), jnp.bfloat16)
    other = KVCacheConfig(**{**plain.__dict__, "slot_states": (
        bf16,) + ccfg.slot_states[1:]})
    assert PagedKVCache(other).compat_key() != PagedKVCache(ccfg).compat_key()
    with pytest.raises(ValueError, match="slot states need distinct"):
        KVCacheConfig(**{**plain.__dict__, "slot_states": (
            SlotState("k", 1, (2,)),)})


def test_prefix_cache_refuses_a_cache_with_state(built):
    _, _, ccfg, fns, fresh = built
    with pytest.raises(ValueError, match="per-slot state"):
        PagedKVCache(ccfg).admit(0, 12, prompt_tokens=list(range(8)))
    with pytest.raises(ValueError, match="per-slot state"):
        ContinuousBatcher(fns.prefill, fns.decode, PagedKVCache(ccfg),
                          fresh(), max_prompt_len=MAX_PROMPT,
                          chunk_fn=fns.chunk, prefill_chunk=CHUNK,
                          prefix_cache=True)


def test_handoff_refuses_a_cache_with_state(built):
    """A handoff moves pages; the slot's state would stay behind."""
    _, _, ccfg, fns, fresh = built
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
        max_prompt_len=MAX_PROMPT, chunk_fn=fns.chunk, prefill_chunk=CHUNK,
        harvest_every=1)
    queue = collections.deque([Request(uid="h", prompt=list(_tokens(50, 6)),
                                       max_new_tokens=8)])
    for _ in range(3):
        batcher.pump(queue)
    with pytest.raises(ValueError, match="cannot export"):
        batcher.export_request("h")
    packet = HandoffPacket(req=Request(uid="i", prompt=[1, 2],
                                       max_new_tokens=2),
                           tokens=[3], staged={}, n_pages=1, written=2,
                           wire_bytes=0,
                           compat_key=batcher.cache.compat_key())
    with pytest.raises(ValueError, match="cannot import"):
        batcher.import_request(packet)
