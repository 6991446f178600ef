"""The fmha_mid kernels compile for a v5e at the benchmark cells' sizes.

No chip is needed: the TPU compiler is installed with jax and compiles
for a chip that is described, not attached — so what Mosaic refuses (a
block that does not fit VMEM, a slice or transpose it cannot lay out)
fails here and not on the chip.  Nothing runs, so this says nothing
about results or times.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu.ops import attention_mid
from apex_tpu.ops import fmha_mid


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (b, h, sq, sk, d, dtype, causal, bias shape, extra keywords)
CALLS = {
    "train-345m": (16, 16, 1024, 1024, 64, jnp.bfloat16, True, None, {}),
    "train-1.3b-dp2tp2": (4, 8, 2048, 2048, 128, jnp.bfloat16, True, None,
                          {}),
    "gpt2-prefill": (1, 16, 960, 960, 64, jnp.bfloat16, True, None, {}),
    "latent-chunk": (1, 32, 2048, 2048, 192, jnp.bfloat16, False,
                     (1, 1, 2048, 2048), {"bias_requires_grad": False}),
    "ring-shard-lse": (2, 8, 1024, 1024, 128, jnp.bfloat16, True, None,
                       {"return_lse": True}),
    "float32-dropout": (2, 4, 640, 640, 64, jnp.float32, True, None,
                        {"dropout_rate": 0.1, "dropout_seed": 3}),
    "per-head-bias-grad": (2, 4, 1024, 1024, 64, jnp.bfloat16, False,
                           (2, 4, 1024, 1024), {}),
    "packed-varlen-segments": (2, 8, 1536, 1536, 64, jnp.bfloat16, True,
                               None, {"segments": True}),
    "cross-attention-ragged": (2, 8, 600, 1100, 80, jnp.bfloat16, False,
                               None, {}),
}


@pytest.mark.parametrize("call", list(CALLS))
def test_forward_and_backward_compile(one_chip, monkeypatch, call):
    b, h, sq, sk, d, dtype, causal, bias_shape, kw = CALLS[call]
    kw = dict(kw)
    segments = kw.pop("segments", False)
    # off the TPU the kernels would be built for the interpreter
    monkeypatch.setattr(attention_mid, "_interpret", lambda: False)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [sds((b, h, sq, d), dtype), sds((b, h, sk, d), dtype),
            sds((b, h, sk, d), dtype)]
    if bias_shape is not None:
        args.append(sds(bias_shape, jnp.float32))
    if segments:
        args.append(sds((b, sq), jnp.int32))

    def loss(q, k, v, *rest):
        segs = dict(q_segment_ids=rest[-1],
                    kv_segment_ids=rest[-1]) if segments else {}
        res = fmha_mid(q, k, v, causal=causal, implementation="pallas",
                       bias=rest[0] if bias_shape else None, **segs, **kw)
        out = res[0] if kw.get("return_lse") else res
        val = jnp.sum(out.astype(jnp.float32))
        if kw.get("return_lse"):
            val = val + jnp.sum(res[1])
        return val

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert "tlm.kernel.fmha_mid.fwd" in text
    assert "tlm.kernel.fmha_mid.bwd" in text


# The paged decode kernel at the serving cells' sizes (in THIS file: one
# process may hold the TPU's library).  (slots, query heads, K/V heads,
# head_dim, table width, window or 0, s_q, int8 pool)
DECODE_CALLS = {
    "gpt2-345m-decode": (32, 16, 16, 64, 16, 0, 1, False),
    "trinity-full-layer": (24, 48, 8, 128, 200, 0, 1, False),
    "trinity-window-layer": (24, 48, 8, 128, 81, 4096, 1, False),
    # the other calls the ONE kernel serves, at widths a chip would see:
    # 16 heads of 128 over a long table (8 pages a step, widened to
    # fp32 a head), a prefill chunk over pages, a verify step, int8
    "mha-128-long-table": (8, 16, 16, 128, 128, 0, 1, False),
    "gpt2-345m-chunk-64": (1, 16, 16, 64, 16, 0, 64, False),
    "mha-128-verify-4": (8, 16, 16, 128, 32, 0, 4, False),
    "gpt2-345m-int8": (32, 16, 16, 64, 16, 0, 1, True),
    "mha-128-int8": (8, 16, 16, 128, 32, 0, 1, True),
}


@pytest.mark.parametrize("call", list(DECODE_CALLS))
def test_paged_decode_compiles(one_chip, monkeypatch, call):
    """Grouped heads as further query rows, the walk from a per-slot
    first position round a ring, the fused rotation, and a step's pages
    copied into one tile a head (or one page a step from the pipeline:
    64-wide heads, int8) lay out for Mosaic at the cell's widths."""
    from apex_tpu.ops import attention_decode

    b, hq, hkv, d, width, window, sq, int8 = DECODE_CALLS[call]
    page = 64
    monkeypatch.setattr(attention_decode, "_interpret", lambda: False)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((1 + b * width, hkv, page, d),
               jnp.int8 if int8 else jnp.bfloat16)
    scales = sds((1 + b * width, hkv, page, 1), jnp.float32)
    rope = sds((b, sq, d // 2), jnp.float32)

    def step(q, k, v, table, lengths, cos, sin, ks, vs):
        return attention_decode.fmha_decode(
            q, k, v, table, lengths, rope=(cos, sin),
            num_kv_heads=hkv if hkv != hq else None,
            first=jnp.maximum(lengths - window, 0) if window else None,
            max_pages=window // page + 1 if window else None,
            k_scales=ks if int8 else None, v_scales=vs if int8 else None,
            implementation="pallas")

    text = jax.jit(step).lower(
        sds((b, hq, sq, d), jnp.bfloat16), pool, pool,
        sds((b, width), jnp.int32), sds((b,), jnp.int32), rope, rope,
        scales, scales,
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "tlm.kernel.paged_decode" in text


# Xing4.0's two kernels at the cell's widths (in THIS file too).
@pytest.mark.parametrize("tokens", [4096, 16], ids=["chunk", "decode"])
def test_hyper_connection_mapping_compiles(one_chip, monkeypatch, tokens):
    """The projection of four float32 streams of 3584 in full precision,
    the transpose that puts the tokens on the lanes and 20 Sinkhorn
    iterations on 4 x 4 values a token: one Mosaic call, for a chunk's
    tokens and for a decode step's sixteen (padded to whole lanes)."""
    from apex_tpu.ops import hyper_connections as hc

    monkeypatch.setattr(hc, "_interpret", lambda: False)
    sds = lambda shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=one_chip)
    text = jax.jit(lambda X, phi, alpha, bias: hc.hc_mapping(
        X, phi, alpha, bias, sinkhorn_iters=20, eps=1e-6,
        clamp=(-30.0, 30.0), rms_eps=1e-6, implementation="pallas")).lower(
        sds((4, tokens, 3584)), sds((4, 24, 3584)), sds((3,)), sds((24,)),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "tlm.kernel.hc_map" in text


def _latent_walk_xing4(one_chip, monkeypatch):
    """``mla_paged`` compiled for the v5e at the Xing4 cell's widths (6
    layers x 16 slots x 336 pages of 64 rows, 640 wide, 32 heads), the
    layer a traced scalar, no selection."""
    from apex_tpu.ops import attention_latent as al

    monkeypatch.setattr(al, "_interpret", lambda: False)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf = jnp.bfloat16
    return jax.jit(lambda qn, qr, pool, layer, table, lengths, uk, uv:
                   al.mla_paged(qn, qr, pool, layer, table, lengths, uk, uv,
                                0.1, implementation="pallas")).lower(
        sds((16, 32, 128), bf), sds((16, 32, 64), bf),
        sds((6, 1 + 16 * 336, 64, 640), bf), sds((), jnp.int32),
        sds((16, 336), jnp.int32), sds((16,), jnp.int32),
        sds((512, 32, 128), bf), sds((512, 32, 128), bf)).compile()


def test_latent_walk_compiles_and_gathers_nothing(one_chip, monkeypatch):
    """The absorbed form over the stacked latent pool of the Xing4 cell
    (6 layers x 16 slots x 336 pages of 64 rows, 640 wide), the layer a
    traced scalar: one Mosaic call and no temporary the size of a
    layer's pool or of a slot's context."""
    compiled = _latent_walk_xing4(one_chip, monkeypatch)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "tlm.kernel.latent_walk" in text
    # one slot's gathered context would be 27.5 MB, a layer's pool 440
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


# the flash forward told its positions, at the chunk shapes of the Xing4
# and Trinity cells: (heads, q rows, keys, width, q_period, window)
POSITIONED_CALLS = {
    "xing4-chunk": (32, 4096, 20480, 192, None, 0),
    "trinity-window-chunk": (8, 6144, 5120, 128, 1024, 4096),
    "trinity-full-chunk": (8, 6144, 13312, 128, 1024, 0),
}


@pytest.mark.parametrize("call", list(POSITIONED_CALLS))
def test_positioned_flash_forward_compiles_and_holds_no_mask(
        one_chip, monkeypatch, request, call):
    """``flash_attention(causal=True, q_offset=<traced>, ...)`` at its
    default blocks: one Mosaic call whose bounds arrive as prefetched
    scalars, and nothing the size of a ``(rows, keys)`` float32 mask in
    the program (the bias variant it replaces held one)."""
    from apex_tpu.ops import attention
    from apex_tpu.utils import platform

    monkeypatch.setattr(attention, "_interpret", lambda: False)
    monkeypatch.setattr(platform, "_current_platform", lambda: "tpu")
    # the positioned call is a jitted function: a trace an earlier test
    # of this process made at these shapes was built for the
    # interpreter, and this one must not be found by a later test
    attention._flash_positioned_once.clear_cache()
    request.addfinalizer(attention._flash_positioned_once.clear_cache)
    h, sq, sk, d, period, window = POSITIONED_CALLS[call]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf = jnp.bfloat16
    compiled = jax.jit(lambda q, k, v, at: attention.flash_attention(
        q, k, v, causal=True, sm_scale=0.1, q_offset=at, q_period=period,
        window=window)).lower(
            sds((1, h, sq, d), bf), sds((1, h, sk, d), bf),
            sds((1, h, sk, d), bf), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "tlm.kernel.fmha_flash.fwd" in text
    assert f"f32[{sq},{sk}]" not in text and f"f32[1,{sq},{sk}]" not in text


def test_grouped_expert_product_compiles_and_copies_no_layer(
        one_chip, monkeypatch):
    """``HeldExpertsMLP.apply`` at Xing4's chunk (4,096 tokens, 4 of 64
    experts each, all held) over the layer-stacked experts with a traced
    layer, as the chunk program's scan calls it: the two ``moe_grouped``
    Mosaic calls (their weight blocks within the VMEM limit they ask
    for), no loop over tiles, and no temporary the size of a layer's
    experts (1.4 GB): the kernel indexes the stack, nothing slices it."""
    from apex_tpu.ops import moe_grouped
    from apex_tpu.transformer.moe import HeldExpertsMLP
    from apex_tpu.utils import platform

    monkeypatch.setattr(moe_grouped, "_interpret", lambda: False)
    monkeypatch.setattr(platform, "_current_platform", lambda: "tpu")
    h, f, experts, k, n, layers = 3584, 1024, 64, 4, 4096, 5
    layer = HeldExpertsMLP(h, f, experts, top_k=k)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: sds(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), experts)))
    params["experts"] = {name: sds((layers,) + leaf.shape, leaf.dtype)
                         for name, leaf in params["experts"].items()}
    compiled = jax.jit(lambda p, x, j: layer.apply(
        p, x, tuple(range(experts)), expert_layer=j)).lower(
            params, sds((n, h), jnp.bfloat16), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "tlm.kernel.moe_grouped.gate_up" in text
    assert "tlm.kernel.moe_grouped.down" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_latent_walk_without_a_selection_compiles_as_it_did(
        one_chip, monkeypatch):
    """The walk Xing4's decode step runs (no selection) compiles to the
    text it had before the walk took an optional selection: the sha256
    of the compiled program with names, source locations and the
    locations inside the Mosaic body taken out
    (``tools/compiled_text_diff.strip_metadata``), taken with jax and
    jaxlib 0.9.0 and libtpu 0.0.34.  A change that means to move this
    program changes the digest with it, and says so.

    A new jax, jaxlib or libtpu may change the text of an unchanged
    kernel.  Then compare the programs of the two commits under the new
    toolchain: ``python tools/compiled_text_diff.py --parent <a checkout
    of the commit before the selection> --change . --programs
    serve-xing4`` must say EQUAL for ``jit__decode``.  Only then is the
    digest this test prints the one to keep."""
    import hashlib
    import os

    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from compiled_text_diff import strip_metadata

    text = strip_metadata(_latent_walk_xing4(one_chip, monkeypatch).as_text())
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == (
        "668bc07f98bf58c579d783aca9cac944688aa19d98c92a844e31b4f3e2341258"), (
        f"digest {digest} under jax {jax.__version__}: see the docstring "
        f"for how to tell a toolchain's change from the kernel's")


def test_latent_walk_under_a_selection_compiles_at_the_dsv32_cell(
        one_chip, monkeypatch):
    """DeepSeek-V3.2's decode attention over the latent pool of its cell
    (5 layers x 32 slots x 112 pages of 64 rows, 128 heads) under a
    (32, 7168) selection: one Mosaic call, the selection its step-sized
    blocks, and no temporary the size of a slot's chosen rows (the
    gathered form's (32, 2048, 640) is 84 MB)."""
    from apex_tpu.ops import attention_latent as al

    monkeypatch.setattr(al, "_interpret", lambda: False)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf = jnp.bfloat16
    compiled = jax.jit(
        lambda qn, qr, pool, layer, table, lengths, uk, uv, sel:
        al.mla_paged(qn, qr, pool, layer, table, lengths, uk, uv, 0.1,
                     selected=sel, implementation="pallas")).lower(
        sds((32, 128, 128), bf), sds((32, 128, 64), bf),
        sds((5, 1 + 32 * 112, 64, 640), bf), sds((), jnp.int32),
        sds((32, 112), jnp.int32), sds((32,), jnp.int32),
        sds((512, 128, 128), bf), sds((512, 128, 128), bf),
        sds((32, 7168), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "tlm.kernel.latent_walk" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**20
