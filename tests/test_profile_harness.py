"""Regression tests for the tools/profile_r05.py decomposition harness.

The r05 capture lost its "fwd+bwd, no optimizer" row to a harness bug:
the variant folds a zero grad-sum into the loss for the data
dependency, and tp-sharded grad leaves made that sum tp-varying — which
the step's ``out_specs P()`` (replicated loss) rejects.  The fix pmeans
the sum back to replicated; this test compiles and runs the EXACT
harness step (``profile_r05.make_step``) on a tp>1 mesh so the bug
class cannot recur silently until the next scarce chip session.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel.layers import state_specs_like

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import profile_r05  # noqa: E402


@pytest.fixture
def tp2_mesh():
    m = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=2
    )
    yield m
    parallel_state.destroy_model_parallel()


def _build_small(mesh):
    model = GPTModel(GPTConfig(
        vocab_size=64, num_layers=1, hidden_size=32,
        num_attention_heads=2, max_position_embeddings=16,
        compute_dtype=jnp.float32, remat=False, attention_impl="xla",
    ))
    params = model.init(jax.random.PRNGKey(0))
    specs = model.param_specs()
    opt = FusedAdam(lr=1e-4, master_weights=True)
    opt_state = opt.init(params)
    opt_specs = state_specs_like(specs, opt_state)
    place = lambda tree, sp: jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), sp,
                           is_leaf=lambda x: isinstance(x, P)))
    return (model, opt, specs, opt_specs,
            place(params, specs), place(opt_state, opt_specs))


# the bug class this file guards is out_specs P() on a tp>1 mesh: the
# no_opt row is the one that failed during the r05 capture
@pytest.mark.parametrize("variant",
                         ["no_opt", "fwd_only", "opt_only", "full"])
def test_variants_compile_and_run_on_tp2(tp2_mesh, variant):
    """Every decomposition variant must compile on a tp>1 mesh."""
    model, opt, specs, opt_specs, params, opt_state = _build_small(tp2_mesh)
    kw = {k: variant == k for k in ("no_opt", "fwd_only", "opt_only")}
    step = profile_r05.make_step(model, opt, tp2_mesh, specs, opt_specs,
                                 **kw)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1)
    params, opt_state, loss = step(params, opt_state, tokens, targets)
    assert jnp.isfinite(jax.device_get(loss))


def test_no_opt_loss_matches_fwd_only(tp2_mesh):
    """The folded zero grad-sum must not perturb the loss value."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1)
    losses = {}
    for variant in ("no_opt", "fwd_only"):
        # rebuild per variant: the step donates params/opt_state, and
        # init is keyed so both variants see identical values
        model, opt, specs, opt_specs, params, opt_state = _build_small(
            tp2_mesh)
        step = profile_r05.make_step(
            model, opt, tp2_mesh, specs, opt_specs,
            no_opt=variant == "no_opt", fwd_only=variant == "fwd_only",
        )
        _, _, loss = step(params, opt_state, tokens, targets)
        losses[variant] = float(jax.device_get(loss))
    assert losses["no_opt"] == pytest.approx(losses["fwd_only"], rel=1e-6)
