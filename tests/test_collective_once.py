"""Nothing is all-reduced twice in a training step (PR 31).

One rule, two sites: a tensor that has been summed over a mesh axis in
this step is kept, never summed again.

1. ``examples/gpt_pretrain.py:dp_mean_where_varying`` averages over dp
   only a gradient that still varies over dp.  ``model.loss`` averages
   inside the differentiated function, so the transposes have summed
   every replicated leaf already; the old ``pmean`` of everything sent
   the whole gradient tree over the wire a second time.
2. A row-parallel layer's output carries ``TP_REDUCED_NAME`` after its
   tp sum and the models' default remat policy keeps that name, so the
   backward does not redo the forward's ``attn_proj`` all-reduce.

The trainer's own step (``main([...])`` as the benchmark's runner calls
it, dp 2 x tp 2 on four of the virtual CPU devices, tiny widths) is
compiled and its collectives are COUNTED by site; a CPU run says
nothing about time.  The old rule is built here, by the test: the
program keeps neither the ``pmean`` of everything nor the policy
without the name.
"""

import contextlib
import functools
import importlib.util
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.ops.common import ATTENTION_RESIDUAL_NAMES
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel import random as tp_random

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEPT = "dots_with_no_batch_dims_and_attention_saveable"
LAYERS, HIDDEN, SEQ, MICRO = 3, 128, 64, 2
ACTIVATION = (MICRO, SEQ, HIDDEN)       # one dp rank's (b, s, h)


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def trainer():
    return _load("gpt_pretrain", "examples", "gpt_pretrain.py")


def _train(trainer, steps):
    """The trainer's own step after ``steps`` optimizer steps in
    float32, dp 2 x tp 2."""
    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    on_four = functools.partial(parallel_state.initialize_model_parallel,
                                devices=jax.devices()[:4])
    try:
        with mock.patch.object(parallel_state, "initialize_model_parallel",
                               on_four):
            return trainer.main([
                "--tp", "2", "--layers", str(LAYERS),
                "--hidden", str(HIDDEN), "--heads", "4",
                "--seq", str(SEQ), "--vocab", "512",
                "--opt-level", "O0", "--micro-batch", str(MICRO),
                "--num-micro", "1", "--steps", str(steps),
                "--log-every", "1000000"])
    finally:
        parallel_state.destroy_model_parallel()


@pytest.fixture(scope="module")
def audit():
    return _load("comm_audit", "tools", "comm_audit.py")


@contextlib.contextmanager
def _old_rule(trainer):
    """The rule before PR 31, both sites: pmean every leaf that is not
    sharded over dp, and the default policy without the tp sum's name."""
    def pmean_everything(grads, specs):
        return jax.tree.map(
            lambda g, sp: (g if "dp" in parallel_state.spec_axis_names(sp)
                           else jax.lax.pmean(g, "dp")), grads, specs)

    without_the_name = jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names(
            *ATTENTION_RESIDUAL_NAMES))
    with mock.patch.object(trainer, "dp_mean_where_varying",
                           pmean_everything), \
            mock.patch.dict(tp_random.CHECKPOINT_POLICIES,
                            {KEPT: without_the_name}):
        yield


# the mesh is (dp 2, pp 1, tp 2) over partitions 0..3, tp innermost
AXIS_GROUPS = {"tp": [[0, 1], [2, 3]], "dp": [[0, 2], [1, 3]]}


def _step_collectives(audit, trainer):
    """The compiled step's collectives, each with the mesh axis its
    replica groups span."""
    out = _train(trainer, steps=0)
    records = audit.parse_collectives(
        out["step"].lower(*out["step_args"]).compile().as_text())
    for rec in records:
        groups = sorted(map(sorted, rec["replica_groups"]))
        rec["axis"] = next(
            (a for a, g in AXIS_GROUPS.items() if g == groups), None)
    assert records and all(r["op_name"] for r in records)
    return records


@pytest.fixture(scope="module")
def collectives(audit, trainer):
    return _step_collectives(audit, trainer)


def _in_body(records, backward: bool):
    return [r for r in records if "/while/body/" in r["op_name"]
            and ("transpose(" in r["op_name"]) == backward]


def _activation_sums(records):
    """tp all-reduces of activation shape; XLA may combine two into
    one tuple, so elements are counted, not instructions."""
    return sum(shape == ACTIVATION
               for r in records if r["axis"] == "tp"
               for _, shape in r["result_shapes"])


def test_no_collective_under_grad_sync(collectives):
    assert [r for r in collectives if r["phase"] == "grad_sync"
            or "tlm.grad_sync" in r["op_name"]] == []
    # the dp gradient reduction is still there, once: under fwd_bwd,
    # put there by the transpose of model.loss's own pmean
    dp = [r for r in collectives if r["axis"] == "dp"
          and r["result_bytes"] > 4]
    assert dp and all("transpose(" in r["op_name"]
                      and r["phase"] == "fwd_bwd" for r in dp)


def test_layer_bodies_hold_four_tp_activation_sums_and_one_dp_tuple(
        collectives):
    forward, backward = (_in_body(collectives, b) for b in (False, True))
    # attn_proj and fc2 partial sums
    assert _activation_sums(forward) == 2
    assert [r["axis"] for r in forward] == ["tp", "tp"]
    # the transposes of the two column-parallel inputs; NOT the
    # forward's attn_proj sum a second time
    assert _activation_sums(backward) == 2
    # this layer's parameter gradients, one tuple
    dp = [r for r in backward if r["axis"] == "dp"]
    assert len(dp) == 1 and len(dp[0]["result_shapes"]) > 1


def test_old_rule_is_what_the_counts_tell_apart(audit, trainer):
    """The same counts on the step built with the old rule: five tp
    activation sums a layer and the gradient tree under grad_sync."""
    with _old_rule(trainer):
        records = _step_collectives(audit, trainer)
    assert [r for r in records if r["phase"] == "grad_sync"]
    assert _activation_sums(_in_body(records, backward=True)) == 3


def test_four_float32_steps_bit_identical_to_the_old_rule(trainer):
    new = _train(trainer, steps=4)
    with _old_rule(trainer):
        old = _train(trainer, steps=4)
    assert new["loss"] == old["loss"]
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(new["params"]),
            jax.tree_util.tree_leaves_with_path(old["params"])):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a), np.asarray(b)), jax.tree_util.keystr(path)


# ---------------------------------------------------------------------------
# the adaptive branch, on both sides
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("loss_means_over_dp", [True, False],
                         ids=["invariant-gradient", "varying-gradient"])
def test_dp_mean_where_varying_matches_single_device(
        audit, trainer, loss_means_over_dp):
    """A loss that averages over dp inside hands over dp-invariant
    gradients: nothing is added.  A loss WITHOUT the internal mean,
    differentiated with respect to weights cast dp-varying (the
    ``parallel.Reducer`` idiom; against dp-invariant weights jax's
    transpose would sum by itself), hands over dp-varying ones: they
    get their pmean.  Either way the gradient is the single-device one
    and went over the wire once; a leaf sharded over dp is left alone."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    w = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
    e = jnp.arange(1.0, 5.0)                       # sharded over dp
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    specs = {"w": P(), "e": P("dp")}

    def local_loss(p, x):
        return jnp.mean((x @ p["w"]) ** 2) + jnp.sum(p["e"] ** 2)

    def step(p, x):
        if loss_means_over_dp:
            g = jax.grad(lambda p: jax.lax.pmean(local_loss(p, x), "dp"))(p)
            assert "dp" not in jax.typeof(g["w"]).vma
        else:
            local = dict(p, w=jax.lax.pcast(p["w"], "dp", to="varying"))
            g = jax.grad(local_loss)(local, x)
            assert "dp" in jax.typeof(g["w"]).vma
        return trainer.dp_mean_where_varying(g, specs), g["e"]

    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(specs, P("dp")),
                               out_specs=(specs, P("dp"))))
    got, e_before = fn({"w": w, "e": e}, x)
    want = jax.grad(lambda w: jnp.mean((x @ w) ** 2))(w)
    np.testing.assert_allclose(got["w"], want, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(got["e"], e_before)

    sums = [r for r in audit.parse_collectives(
        fn.lower({"w": w, "e": e}, x).compile().as_text())
        if ("f32", w.shape) in r["result_shapes"]]
    assert len(sums) == 1
    assert sorted(map(sorted, sums[0]["replica_groups"])) == AXIS_GROUPS["dp"]
