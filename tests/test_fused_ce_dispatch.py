"""Unit tests for the fused-CE auto-dispatch adopted after the r05
profile: ``fused_ce=None`` picks the two-step path below
``FUSED_CE_AUTO_BYTES`` of materialized logits and the fused
online-logsumexp scan above it (transformer/tensor_parallel/
cross_entropy.py), threaded through ``models/gpt.py``.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel import cross_entropy as ce


class TestAutoRule:
    def test_boundary_exact_bytes_takes_two_step(self, monkeypatch):
        # the rule is STRICTLY greater-than: logits of exactly the
        # threshold size stay on the faster two-step path
        monkeypatch.setattr(ce, "FUSED_CE_AUTO_BYTES", 4096)
        assert ce.fused_ce_auto(32, 32) is False      # 32*32*4 == 4096

    def test_boundary_one_element_over_takes_fused(self, monkeypatch):
        monkeypatch.setattr(ce, "FUSED_CE_AUTO_BYTES", 4096)
        assert ce.fused_ce_auto(32, 33) is True       # 4224 > 4096

    def test_flagship_residual_takes_two_step(self):
        # the r05-adopted decision at the flagship config: the 1.07 GB
        # (8192 tokens x 32768 vocab) fp32 residual sits under the
        # 2 GiB default and runs the measured-faster two-step path
        assert ce.fused_ce_auto(8192, 32768) is False

    def test_just_over_default_takes_fused(self):
        assert ce.fused_ce_auto(8192, (2 << 30) // (8192 * 4) + 1) is True

    def test_env_override_round_trip(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_FUSED_CE_BYTES", "1024")
        try:
            importlib.reload(ce)
            assert ce.FUSED_CE_AUTO_BYTES == 1024
            assert ce.fused_ce_auto(16, 16) is False  # 1024 == 1024
            assert ce.fused_ce_auto(16, 17) is True
        finally:
            monkeypatch.delenv("APEX_TPU_FUSED_CE_BYTES")
            importlib.reload(ce)
        assert ce.FUSED_CE_AUTO_BYTES == 2 << 30


class TestGPTDispatch:
    """``GPTConfig(fused_ce=None)`` must route through the auto rule —
    spied at the two cross_entropy entry points the dispatcher picks
    between."""

    @pytest.fixture
    def mesh(self):
        m = parallel_state.initialize_model_parallel()
        yield m
        parallel_state.destroy_model_parallel()

    def _loss(self, mesh, model, calls, monkeypatch):
        fused_orig = ce.vocab_parallel_cross_entropy_from_hidden
        twostep_orig = ce.vocab_parallel_cross_entropy

        def spy_fused(*a, **kw):
            calls.append("fused")
            return fused_orig(*a, **kw)

        def spy_twostep(*a, **kw):
            calls.append("two_step")
            return twostep_orig(*a, **kw)

        monkeypatch.setattr(
            ce, "vocab_parallel_cross_entropy_from_hidden", spy_fused)
        monkeypatch.setattr(
            ce, "vocab_parallel_cross_entropy", spy_twostep)
        specs = model.param_specs()
        params = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P)))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        fn = jax.jit(jax.shard_map(
            model.loss, mesh=mesh,
            in_specs=(specs, P("dp"), P("dp")), out_specs=P(),
        ))
        return float(jax.device_get(
            fn(params, tokens, jnp.roll(tokens, -1, axis=1))))

    def _model(self, fused_ce=None):
        return GPTModel(GPTConfig(
            vocab_size=64, num_layers=1, hidden_size=32,
            num_attention_heads=2, max_position_embeddings=16,
            compute_dtype=jnp.float32, remat=False, attention_impl="xla",
            fused_ce=fused_ce,
        ))

    def test_auto_small_logits_two_step(self, mesh, monkeypatch):
        calls = []
        loss = self._loss(mesh, self._model(fused_ce=None), calls,
                          monkeypatch)
        # 32 tokens x 64 vocab sits far under the threshold
        assert "two_step" in calls and "fused" not in calls
        assert np.isfinite(loss)

    def test_auto_above_threshold_fused(self, mesh, monkeypatch):
        monkeypatch.setattr(ce, "FUSED_CE_AUTO_BYTES", 1)
        calls = []
        loss = self._loss(mesh, self._model(fused_ce=None), calls,
                          monkeypatch)
        assert "fused" in calls and "two_step" not in calls
        assert np.isfinite(loss)

    def test_forced_paths_ignore_threshold(self, mesh, monkeypatch):
        # fused_ce=True / False must win over any threshold setting
        monkeypatch.setattr(ce, "FUSED_CE_AUTO_BYTES", 1)
        calls = []
        self._loss(mesh, self._model(fused_ce=False), calls, monkeypatch)
        assert "two_step" in calls and "fused" not in calls
        monkeypatch.setattr(ce, "FUSED_CE_AUTO_BYTES", 2 << 30)
        calls = []
        self._loss(mesh, self._model(fused_ce=True), calls, monkeypatch)
        assert "fused" in calls and "two_step" not in calls

    def test_auto_matches_forced_numerics(self, mesh, monkeypatch):
        calls = []
        auto = self._loss(mesh, self._model(fused_ce=None), calls,
                          monkeypatch)
        forced = self._loss(mesh, self._model(fused_ce=False), calls,
                            monkeypatch)
        assert auto == pytest.approx(forced, rel=1e-6)
