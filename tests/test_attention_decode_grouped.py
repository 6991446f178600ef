"""``fmha_decode`` with grouped-query heads and a per-sequence first
position over a ring table, against a dense ``jnp`` attention that knows
nothing of pages; and its one-K/V-head-a-query-head case, bit-equal to
the walk from position 0."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention_decode import fmha_decode

PS, D = 4, 16


def _ring_cache(key, lengths, h_kv, ring, window):
    """Dense K/V (b, h_kv, L, d) and the SAME tokens laid out as a ring
    cache: position p of sequence b in column (p // PS) % ring of its
    row, every page of the pool another one."""
    b, L = len(lengths), max(lengths)
    kk, kv = jax.random.split(key)
    k = jax.random.normal(kk, (b, h_kv, L, D), jnp.float32)
    v = jax.random.normal(kv, (b, h_kv, L, D), jnp.float32)
    table = 1 + np.arange(b * ring, dtype=np.int32).reshape(b, ring)
    table = table[:, np.random.default_rng(0).permutation(ring)]
    kp = np.zeros((1 + b * ring, h_kv, PS, D), np.float32)
    vp = np.zeros_like(kp)
    for i, ln in enumerate(lengths):
        for p in range(ln):             # later positions overwrite
            page = table[i, (p // PS) % ring]
            kp[page, :, p % PS] = np.asarray(k[i, :, p])
            vp[page, :, p % PS] = np.asarray(v[i, :, p])
    return k, v, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table)


def _dense(q, k, v, lengths, window, group):
    """softmax(q k / sqrt(d)) v over positions [ln - window, ln) of each
    sequence, query head i on K/V head i // group."""
    outs = []
    for i, ln in enumerate(lengths):
        lo = max(ln - window, 0)
        ki = jnp.repeat(k[i, :, lo:ln], group, axis=0)
        vi = jnp.repeat(v[i, :, lo:ln], group, axis=0)
        s = jnp.einsum("hqd,hkd->hqk", q[i], ki) / D ** 0.5
        outs.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), vi))
    return jnp.stack(outs)


@pytest.mark.parametrize("implementation", ["xla", "pallas"])
@pytest.mark.parametrize("group,window", [(3, 8), (1, 8), (2, 10 ** 6)])
def test_grouped_windowed_matches_dense(implementation, group, window):
    h_kv, ring = 2, 4                   # ring: window 8 + two pages
    lengths = [3, 8, 21, 38]            # inside the window .. 2 wraps
    if window > 100:
        ring = 10                       # the whole context, never wraps
    k, v, kp, vp, table = _ring_cache(
        jax.random.PRNGKey(1), lengths, h_kv, ring, window)
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (len(lengths), h_kv * group, 1, D), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    got = fmha_decode(
        q, kp, vp, table, ln, num_kv_heads=h_kv,
        first=jnp.maximum(ln - window, 0),
        max_pages=min(window // PS + 1, ring),
        implementation=implementation)
    np.testing.assert_allclose(
        got, _dense(q, k, v, lengths, window, group), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("implementation", ["xla", "pallas"])
def test_grouped_from_zero_without_first(implementation):
    """Grouped heads over a plain table (no ``first``): the full layers'
    call."""
    h_kv, group, lengths = 2, 3, [5, 17]
    k, v, kp, vp, table = _ring_cache(
        jax.random.PRNGKey(3), lengths, h_kv, 5, 10 ** 6)
    q = jax.random.normal(jax.random.PRNGKey(4),
                          (2, h_kv * group, 1, D), jnp.float32)
    got = fmha_decode(q, kp, vp, table, jnp.asarray(lengths, jnp.int32),
                      num_kv_heads=h_kv, implementation=implementation)
    np.testing.assert_allclose(
        got, _dense(q, k, v, lengths, 10 ** 6, group), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("implementation", ["xla", "pallas"])
def test_mha_case_bit_equal(implementation):
    """One K/V head a query head, walked from 0: the extended entry
    (``num_kv_heads == heads``; and ``first == 0`` over a table that
    never wraps) gives today's kernel's bits."""
    h, lengths = 4, [6, 19, 40]
    k, v, kp, vp, table = _ring_cache(
        jax.random.PRNGKey(5), lengths, h, 10, 10 ** 6)
    q = jax.random.normal(jax.random.PRNGKey(6), (3, h, 1, D), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    today = fmha_decode(q, kp, vp, table, ln,
                        implementation=implementation)
    same = fmha_decode(q, kp, vp, table, ln, num_kv_heads=h,
                       implementation=implementation)
    from_zero = fmha_decode(q, kp, vp, table, ln, num_kv_heads=h,
                            first=jnp.zeros_like(ln),
                            implementation=implementation)
    assert np.array_equal(today, same)
    if implementation == "pallas":      # the XLA path sums in another order
        assert np.array_equal(today, from_zero)
    else:
        np.testing.assert_allclose(today, from_zero, rtol=1e-6, atol=1e-6)


def test_fused_rotation_with_grouped_rows():
    """The kernel rotates q itself (``q cos + rotate_half(q) sin`` on
    the packed rows): the same as rotating q first."""
    from apex_tpu.ops.rope import apply_rope_tables, rope_cos_sin

    h_kv, group, lengths = 2, 3, [5, 17, 38]
    k, v, kp, vp, table = _ring_cache(
        jax.random.PRNGKey(7), lengths, h_kv, 4, 8)
    q = jax.random.normal(jax.random.PRNGKey(8),
                          (3, h_kv * group, 1, D), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    cos, sin = rope_cos_sin(ln - 1, D)
    kw = dict(num_kv_heads=h_kv, first=jnp.maximum(ln - 8, 0), max_pages=3)
    fused = fmha_decode(q, kp, vp, table, ln, implementation="pallas",
                        rope=(cos[:, None], sin[:, None]), **kw)
    rotated = apply_rope_tables(q, cos[:, None, None], sin[:, None, None])
    for impl in ("pallas", "xla"):
        np.testing.assert_allclose(
            fused, fmha_decode(rotated, kp, vp, table, ln,
                               implementation=impl, **kw),
            rtol=2e-5, atol=2e-5)


def test_refusals():
    q = jnp.zeros((1, 4, 1, D))
    kp = jnp.zeros((3, 3, PS, D))
    with pytest.raises(ValueError, match="pool heads"):
        fmha_decode(q, kp, kp, jnp.zeros((1, 2), jnp.int32),
                    jnp.ones((1,), jnp.int32), num_kv_heads=3)
    kp = jnp.zeros((3, 2, PS, D))
    with pytest.raises(ValueError, match="first must be"):
        fmha_decode(q, kp, kp, jnp.zeros((1, 2), jnp.int32),
                    jnp.ones((1,), jnp.int32), num_kv_heads=2,
                    first=jnp.zeros((2,), jnp.int32))


# ---------------------------------------------------------------------------
# Several pages a grid step (PR 34)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pages", [1, 2, 3, 8])
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("group", [1, 6])
def test_pages_a_step_match_reference(group, ring, pages):
    """One head or six a K/V head, from position 0 or from a first
    position round a ring that has wrapped twice, at every count of
    pages a step the rule gives a table of this shape (its width chooses
    it): contexts that end one token into a step, on a step's last
    token, at 0 (an idle slot) and inside the first step."""
    from apex_tpu.ops import attention_decode as ad
    from apex_tpu.ops.attention_decode import paged_attention_reference

    ps, d, h_kv, width = 16, 128, 2, 8 * pages
    step = pages * ps
    if ring:
        # the window is 7 steps of pages: a walk covers them and the
        # page the newest token sits in
        window = 7 * step
        base = 2 * width * ps + 3 * ps
        lengths = [base + 1, base, 0, ps + 3]
        kw = dict(first=jnp.maximum(jnp.asarray(lengths) - window, 0),
                  max_pages=window // ps + 1)
        walked = min(width, window // ps + 1)
    else:
        lengths = [5 * step + 1, 3 * step, 0, 8 * step]
        kw, walked = {}, width
    assert ad._pages_per_step(
        ps, d, ad._pick_block_h(h_kv, group), 4, walked, False) == pages
    b = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(pages), 4)
    q = jax.random.normal(keys[0], (b, h_kv * group, 1, d), jnp.float32)
    kp = jax.random.normal(keys[1], (1 + b * width, h_kv, ps, d))
    vp = jax.random.normal(keys[2], (1 + b * width, h_kv, ps, d))
    table = 1 + jax.random.permutation(keys[3], b * width).reshape(b, width)
    ln = jnp.asarray(lengths, jnp.int32)
    out = fmha_decode(q, kp, vp, table, ln, num_kv_heads=h_kv,
                      implementation="pallas", **kw)
    want = paged_attention_reference(q, kp, vp, table, ln,
                                     first=kw.get("first"))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(out)).all()


def _equations(jaxpr) -> int:
    """Equations of a jaxpr, those of every jaxpr it holds included (a
    Mosaic call's kernel and its block specs' index maps too)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    n += _equations(sub)
            for spec in getattr(v, "block_mappings", ()):
                n += _equations(spec.index_map_jaxpr)
    return n


def test_the_kernels_size_does_not_grow_with_the_pages_a_step(monkeypatch):
    """What refused PR 33: eight pages a step as eight copies of the
    body a head, traced and lowered at five call sites in every process.
    Trinity's window walk at the rule's pages a step is no more
    equations than at one (and no more than the kernel through PR 33,
    674 at this shape)."""
    from apex_tpu.ops import attention_decode as ad

    b, hq, h_kv, d, ps, width = 24, 48, 8, 128, 64, 81
    sds = jax.ShapeDtypeStruct
    pool = sds((4 * (1 + b * width), h_kv, ps, d), jnp.bfloat16)
    rope = sds((b, 1, d // 2), jnp.float32)

    def walk(q, k, v, table, ln, first, cos, sin):
        return fmha_decode(q, k, v, table, ln, num_kv_heads=h_kv,
                           first=first, max_pages=65, rope=(cos, sin),
                           implementation="pallas")

    count = lambda: _equations(jax.make_jaxpr(walk)(
        sds((b, hq, 1, d), jnp.bfloat16), pool, pool,
        sds((b, width), jnp.int32), sds((b,), jnp.int32),
        sds((b,), jnp.int32), rope, rope))
    assert ad._pages_per_step(ps, d, 8, 2, 65, False) == 8
    at_the_rules = count()
    monkeypatch.setattr(ad, "_pages_per_step", lambda *a: 1)
    at_one = count()
    assert at_the_rules <= 1.25 * at_one, (at_the_rules, at_one)
    assert at_the_rules <= 674, at_the_rules


def test_the_decode_programs_kernels_lower_to_a_bounded_text(monkeypatch):
    """``AfmoeModel.decode_fns``'s ``jit__decode`` (a small model with
    Trinity's head width and layer kinds), lowered for the TPU without
    one: its five paged-decode calls are no more than 160 kB of module
    text (135 kB when written; 77 kB through PR 33 at two K/V heads;
    eight unrolled pages a head would be some 600)."""
    from jax.sharding import Mesh

    from apex_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig, AfmoeModel
    from apex_tpu.serving.kv_cache import KVCacheConfig, init_pools
    from apex_tpu.serving.serve import init_carry
    from apex_tpu.utils import platform

    monkeypatch.setattr(platform, "_current_platform", lambda: "tpu")
    model = AfmoeModel(AfmoeConfig.from_hf(dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=5,
        num_dense_layers=1, num_attention_heads=12, num_key_value_heads=2,
        head_dim=128, intermediate_size=128, moe_intermediate_size=32,
        num_experts_per_tok=2, num_shared_experts=1, route_scale=2.448,
        rms_norm_eps=1e-5, rope_theta=10000.0, mup_enabled=True,
        sliding_window=64, layer_types=[SLIDING] * 4 + [FULL]),
        num_experts=16, held_experts=(1, 4, 6, 11),
        params_dtype=jnp.bfloat16))
    slots = 3
    ccfg = KVCacheConfig.of_classes(
        model.cache_classes(slots=slots, pages_per_seq=64, page_size=16,
                            prefill_chunk=32),
        page_size=16, max_seqs=slots, dtype=jnp.bfloat16)
    fns = model.decode_fns(
        None, Mesh(np.array(jax.devices()[:1]), ("tp",)), ccfg,
        max_prompt_len=256, prefill_chunk=32)
    text = fns.decode_jit.trace(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: init_pools(ccfg)),
        jax.eval_shape(lambda: dict(init_carry(slots),
                                    **fns.decode.carry_extras)),
        jax.ShapeDtypeStruct((slots, ccfg.table_columns[-1][1]), jnp.int32),
    ).lower(lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.split("\n") if "tpu_custom_call" in line]
    assert len(calls) == 5
    assert all("paged_decode" in line for line in calls)
    assert sum(map(len, calls)) <= 160_000, sum(map(len, calls))
