"""The attention forward runs once a training step.

Each training attention kernel's ``custom_vjp`` forward rule tags its
two residuals (``out``, ``lse``) with ``checkpoint_name``
(``ops.common.name_attention_residuals``); the models' default remat
policy (``dots_with_no_batch_dims_and_attention_saveable``) keeps those
names, so the backward of a scanned ``jax.checkpoint`` layer reads them
instead of running the forward kernel a second time.  Checked here on
the CPU with the kernels forced (interpret mode; ``auto`` resolves to
XLA off the TPU):

- the gradient's jaxpr holds the forward ``pallas_call`` once per layer
  body and the compiled text names it nowhere under
  ``rematted_computation``; under ``nothing_saveable`` and under the
  plain dots policy it is there twice (mid, flash and short);
- loss and every gradient leaf are bit-identical to the plain dots
  policy's (the same kernel gave the same values; only its second run
  is gone): mid, flash and short, with dropout, and through
  ``ring_attention``'s ``return_lse=True``;
- the XLA attention path carries no tag and compiles to the same text
  under both policies.

Since PR 31 the default policy keeps a third name, a row-parallel
layer's output after its tp sum (``mappings.TP_REDUCED_NAME``), so the
model-level comparisons above are made against the default policy
without its attention half (``DOTS_AND_TP_SUM``), and at tp 2 the
gradient's jaxpr holds one tp activation sum fewer a layer body than
under the plain dots policy.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from apex_tpu.models import BertConfig, GPTConfig, GPTModel, T5Config
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.common import ATTENTION_RESIDUAL_NAMES
from apex_tpu.ops.ring_attention import ring_attention
from apex_tpu.telemetry.spans import kernel_name
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel.mappings import TP_REDUCED_NAME
from apex_tpu.transformer.tensor_parallel.random import (
    CHECKPOINT_POLICIES,
    checkpoint,
)

KEPT = "dots_with_no_batch_dims_and_attention_saveable"
DOTS = "dots_with_no_batch_dims_saveable"
NOTHING = "nothing_saveable"
# the default policy without its attention half
DOTS_AND_TP_SUM = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(TP_REDUCED_NAME))
# rung -> (``implementation=`` of flash_attention, kernel name, backward
# kernel calls)
KERNELS = {"mid": ("mid", "fmha_mid", 1), "flash": ("pallas", "fmha_flash", 2),
           "short": ("short", "fmha_short", 1)}

B, H, S, D, LAYERS = 2, 2, 16, 8, 2
HID = H * D


def _kernel_calls(jaxpr, name: str) -> int:
    """``pallas_call`` equations, at any depth, whose name holds
    ``name`` (a scan's body is counted once, as it is compiled once)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += name in eqn.params["name"]
        n += sum(_kernel_calls(sub, name)
                 for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


def _op_names(fn, *args):
    return set(re.findall(r'op_name="([^"]*)"',
                          jax.jit(fn).lower(*args).compile().as_text()))


def _same_bits(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# a scanned remat layer around one kernel: qkv dot -> attention -> out dot
# ---------------------------------------------------------------------------
def _stack(attend, policy):
    def body(x, scanned):
        wqkv, wo, seed = scanned
        qkv = (x @ wqkv).reshape(x.shape[0], x.shape[1], 3, H, D)
        q, k, v = (jnp.transpose(qkv[:, :, i], (0, 2, 1, 3))
                   for i in range(3))
        a = jnp.transpose(attend(q, k, v, seed), (0, 2, 1, 3))
        return x + jnp.tanh(a.reshape(x.shape) @ wo), None

    body = checkpoint(body, policy=policy)

    def loss(weights, seeds, x):
        y, _ = jax.lax.scan(body, x, (*weights, seeds))
        return jnp.sum(y ** 2)

    return jax.value_and_grad(loss)


def _kernel_stack(kernel, policy, dropout=0.0):
    return _stack(lambda q, k, v, seed: flash_attention(
        q, k, v, causal=True, implementation=KERNELS[kernel][0],
        dropout_rate=dropout, dropout_seed=seed), policy)


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    weights = (jax.random.normal(ks[0], (LAYERS, HID, 3 * HID)) * 0.2,
               jax.random.normal(ks[1], (LAYERS, HID, HID)) * 0.2)
    seeds = jnp.arange(LAYERS, dtype=jnp.int32) + 7
    return weights, seeds, jax.random.normal(ks[2], (B, S, HID))


@pytest.mark.parametrize("policy,fwd_calls", [(KEPT, 1), (DOTS, 2),
                                              (NOTHING, 2)])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_forward_kernel_calls_in_the_gradient(
        operands, kernel, policy, fwd_calls):
    """Once per layer body when the policy keeps the named residuals,
    and then nowhere under ``rematted_computation``; a second time under
    the plain dots policy (a Mosaic call is not a dot) and under
    ``nothing_saveable``, which a job at its memory limit still has."""
    _, name, bwd_calls = KERNELS[kernel]
    fwd = kernel_name(name + ".fwd")
    step = _kernel_stack(kernel, policy)
    jaxpr = jax.make_jaxpr(step)(*operands).jaxpr
    assert _kernel_calls(jaxpr, fwd) == fwd_calls
    assert _kernel_calls(jaxpr, kernel_name(name + ".bwd")) == bwd_calls
    names = _op_names(step, *operands)
    assert any(fwd in n and "rematted_computation" not in n for n in names)
    assert any(fwd in n and "rematted_computation" in n
               for n in names) == (fwd_calls == 2)


@pytest.mark.parametrize("dropout", [0.0, 0.25], ids=["plain", "dropout"])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_kept_residuals_change_no_bit(operands, kernel, dropout):
    kept = jax.jit(_kernel_stack(kernel, KEPT, dropout))(*operands)
    plain = jax.jit(_kernel_stack(kernel, DOTS, dropout))(*operands)
    assert _same_bits(kept, plain)
    assert all(bool(jnp.all(jnp.isfinite(x))) and bool(jnp.any(x != 0))
               for x in jax.tree.leaves(kept))
    if dropout:     # the mask is live, and replayed the same
        dry = jax.jit(_kernel_stack(kernel, KEPT))(*operands)
        assert not _same_bits(kept[0], dry[0])


# ---------------------------------------------------------------------------
# ring attention: (out, lse) are PRIMAL outputs of the kernel there
# ---------------------------------------------------------------------------
@pytest.fixture
def cp_mesh():
    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        context_parallel_size_=2, devices=jax.devices()[:2])
    yield mesh
    parallel_state.destroy_model_parallel()


@pytest.mark.parametrize("inner_remat", [False, True])
def test_ring_attention_with_lse_keeps_the_same_values(
        cp_mesh, operands, inner_remat):
    def ring_stack(policy):
        step = _stack(lambda q, k, v, seed: ring_attention(
            q, k, v, causal=True, remat=inner_remat,
            attention_impl="mid"), policy)

        def local(weights, seeds, x):
            loss, grads = step(weights, seeds, x)
            return jax.tree.map(lambda g: jax.lax.psum(g, "cp"),
                                (loss, grads))

        return jax.shard_map(
            local, mesh=cp_mesh, in_specs=(P(), P(), P(None, "cp")),
            out_specs=(P(), P()))

    kept, plain = ring_stack(KEPT), ring_stack(DOTS)
    assert _same_bits(jax.jit(kept)(*operands), jax.jit(plain)(*operands))
    fwd = kernel_name("fmha_mid.fwd")
    # two ring blocks a layer body (the diagonal one and the one before
    # it), each run once; without the tags once more for the layer's
    # remat, and once more again for ring_attention's own per-block
    # jax.checkpoint, whose recomputation the kept names spare as well
    assert _kernel_calls(jax.make_jaxpr(kept)(*operands).jaxpr, fwd) == 2
    assert _kernel_calls(jax.make_jaxpr(plain)(*operands).jaxpr, fwd) == (
        6 if inner_remat else 4)


# ---------------------------------------------------------------------------
# the model: GPTModel.loss under shard_map, as the trainers call it
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mesh():
    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    yield mesh
    parallel_state.destroy_model_parallel()


def _gpt_step(mesh, **overrides):
    cfg = dict(vocab_size=64, num_layers=2, hidden_size=32,
               num_attention_heads=4, max_position_embeddings=16,
               compute_dtype=jnp.float32, attention_impl="mid")
    cfg.update(overrides)
    model = GPTModel(GPTConfig(**cfg))
    specs = model.param_specs()
    step = jax.shard_map(
        lambda p, t, y, key: jax.value_and_grad(model.loss)(p, t, y, key),
        mesh=mesh, in_specs=(specs, P("dp"), P("dp"), P()),
        out_specs=(P(), specs))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    return step, (model.init(jax.random.PRNGKey(0)), tokens,
                  jnp.roll(tokens, -1, axis=1), jax.random.PRNGKey(5))


@pytest.mark.parametrize("policy,fwd_calls", [(None, 1), (NOTHING, 2)],
                         ids=["default", NOTHING])
def test_gpt_train_step_holds_the_forward_kernel(mesh, policy, fwd_calls):
    over = {} if policy is None else {"remat_policy": policy}
    step, args = _gpt_step(mesh, **over)
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    assert _kernel_calls(jaxpr, kernel_name("fmha_mid.fwd")) == fwd_calls
    assert _kernel_calls(jaxpr, kernel_name("fmha_mid.bwd")) == 1


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["plain", "dropout"])
def test_gpt_loss_and_grads_bit_identical_to_the_dots_policy(mesh, dropout):
    drop = dict(attention_dropout=dropout, hidden_dropout=dropout)
    step, args = _gpt_step(mesh, **drop)
    plain, _ = _gpt_step(mesh, remat_policy=DOTS_AND_TP_SUM, **drop)
    kept = jax.jit(step)(*args)
    assert _same_bits(kept, jax.jit(plain)(*args))
    assert all(bool(jnp.any(g != 0)) for g in jax.tree.leaves(kept[1]))


def test_xla_attention_compiles_the_same_under_both_policies(mesh):
    """No tag on the XLA path: the named-residual half of the policy
    finds nothing to keep, and the program is the dots policy's."""
    texts = []
    for policy in (KEPT, DOTS_AND_TP_SUM):
        step, args = _gpt_step(mesh, attention_impl="xla",
                               remat_policy=policy)
        texts.append(jax.jit(step).lower(*args).compile().as_text())
    assert texts[0] == texts[1]
    assert "rematted_computation" in texts[0]


def _tp_activation_sums(jaxpr, shape) -> int:
    """``psum`` equations over tp, at any depth, whose result has
    ``shape`` (a scan's body is counted once)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("psum"):
            n += ("tp" in eqn.params["axes"]
                  and eqn.outvars[0].aval.shape == shape)
        n += sum(_tp_activation_sums(sub, shape)
                 for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


def test_tp_sum_is_a_kept_residual_at_tp2():
    """The default policy keeps ``TP_REDUCED_NAME``: the backward body
    no longer redoes the forward's ``attn_proj`` sum (the dots policy
    keeps the dot's output, which is the PARTIAL sum)."""
    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=2, devices=jax.devices()[:2])
    try:
        counts = {}
        for name, policy in (("kept", KEPT), ("dots", DOTS)):
            step, args = _gpt_step(mesh, attention_impl="xla",
                                   remat_policy=policy)
            counts[name] = _tp_activation_sums(
                jax.make_jaxpr(step)(*args).jaxpr, (2, 16, 32))
        # the embedding's sum, two a forward body, the transposes of
        # the two column-parallel inputs a backward body and of the
        # head's; the dots policy redoes attn_proj's besides
        assert counts == {"kept": 6, "dots": 7}
    finally:
        parallel_state.destroy_model_parallel()


def test_policy_table_adds_one_entry_and_the_models_default_to_it():
    jax_names = {"nothing_saveable", "dots_saveable",
                 "dots_with_no_batch_dims_saveable", "everything_saveable"}
    assert set(CHECKPOINT_POLICIES) == jax_names | {KEPT}
    for name in jax_names:      # jax's own meaning, a job at its memory
        assert CHECKPOINT_POLICIES[name] is getattr(   # limit relies on it
            jax.checkpoint_policies, name)
    assert ATTENTION_RESIDUAL_NAMES == ("fmha_out", "fmha_lse")
    assert TP_REDUCED_NAME == "tp_reduced"

    def residuals(name):   # the argument, and the named value if kept
        return len(saved_residuals(jax.checkpoint(
            lambda x: jnp.sin(checkpoint_name(jnp.sin(x), name)),
            policy=CHECKPOINT_POLICIES[KEPT]), jnp.ones(4)))

    assert [residuals(name) for name in (
        *ATTENTION_RESIDUAL_NAMES, TP_REDUCED_NAME, "another")] == [2, 2, 2, 1]
    assert (GPTConfig().remat_policy == BertConfig().remat_policy
            == T5Config(vocab_size=8).remat_policy == KEPT)
