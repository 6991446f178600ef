"""ResNet model + examples smoke tests (the reference's L1 tier runs its
examples as tests; same idea at unit scale, SURVEY.md §4)."""

import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.models.resnet import ResNet, ResNetConfig
from apex_tpu.transformer import parallel_state


def small_resnet(depth=18, sync_bn_axis=None):
    return ResNet(ResNetConfig(
        depth=depth, num_classes=10, width=8,
        compute_dtype=jnp.float32, sync_bn_axis=sync_bn_axis,
    ))


class TestResNet:
    def test_forward_shapes_and_stats_update(self):
        model = small_resnet()
        params, stats = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits, new_stats = model.apply(params, stats, x, training=True)
        assert logits.shape == (2, 10)
        assert np.all(np.isfinite(np.asarray(logits)))
        assert not np.allclose(
            np.asarray(new_stats["bn_stem"]["mean"]),
            np.asarray(stats["bn_stem"]["mean"]),
        )

    def test_eval_uses_running_stats(self):
        model = small_resnet()
        params, stats = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits1, s1 = model.apply(params, stats, x, training=False)
        logits2, s2 = model.apply(params, stats, x, training=False)
        np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits2))
        # eval must not touch running stats
        for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(stats)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resnet50_builds(self):
        model = ResNet(ResNetConfig(depth=50, num_classes=10, width=8,
                                    compute_dtype=jnp.float32,
                                    sync_bn_axis=None))
        params, stats = model.init(jax.random.PRNGKey(0))
        n_params = sum(int(jnp.size(l)) for l in jax.tree.leaves(params))
        assert n_params > 1e5
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3))
        logits, _ = model.apply(params, stats, x)
        assert logits.shape == (1, 10)

    def test_sync_bn_matches_single_device(self):
        """dp=8-sharded batch with SyncBN == whole batch on one device."""
        mesh = parallel_state.initialize_model_parallel()
        try:
            model_sync = small_resnet(sync_bn_axis="dp")
            model_local = small_resnet(sync_bn_axis=None)
            params, stats = model_local.init(jax.random.PRNGKey(0))
            x = jax.random.normal(jax.random.PRNGKey(1), (16, 16, 16, 3))
            ref_logits, _ = model_local.apply(params, stats, x, training=True)


            pspec = jax.tree.map(lambda _: P(), params)
            sspec = jax.tree.map(lambda _: P(), stats)
            fn = jax.jit(
                jax.shard_map(
                    lambda p, s, x: model_sync.apply(p, s, x, training=True),
                    mesh=mesh,
                    in_specs=(pspec, sspec, P("dp")),
                    out_specs=(P("dp"), sspec),
                )
            )
            logits, _ = fn(params, stats, x)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(ref_logits), rtol=5e-3,
                atol=5e-4,
            )
        finally:
            parallel_state.destroy_model_parallel()


@pytest.mark.parametrize(
    "script,args",
    [
        ("examples/simple_distributed.py", []),
        ("examples/dcgan_amp.py", ["--steps", "10", "--batch", "16"]),
        ("examples/imagenet_amp.py",
         ["--depth", "18", "--batch-size", "1", "--image-size", "32",
          "--epochs", "1", "--steps-per-epoch", "2", "--eval-steps", "1",
          "--num-classes", "10"]),
        ("examples/gpt_pretrain.py",
         ["--tp", "2", "--pp", "2", "--num-micro", "2", "--vocab", "64",
          "--layers", "2", "--hidden", "32", "--heads", "4",
          "--seq", "16", "--micro-batch", "1", "--steps", "3"]),
        ("examples/gpt_pretrain.py",
         ["--pp", "2", "--num-micro", "2", "--vocab", "64",
          "--layers", "2", "--hidden", "32", "--heads", "4",
          "--seq", "16", "--micro-batch", "1", "--steps", "3",
          "--zero", "--opt-level", "O2"]),
        ("examples/gpt_pretrain.py",
         ["--pp", "2", "--num-micro", "2", "--vocab", "64",
          "--layers", "2", "--hidden", "32", "--heads", "4",
          "--seq", "16", "--micro-batch", "1", "--steps", "3",
          "--zero", "--num-experts", "8"]),
        ("examples/gpt_pretrain.py",
         ["--vocab", "64", "--layers", "2", "--hidden", "32",
          "--heads", "4", "--seq", "16", "--micro-batch", "1",
          "--steps", "3", "--num-experts", "8"]),
        ("examples/gpt_pretrain.py",
         ["--vocab", "64", "--layers", "2", "--hidden", "32",
          "--heads", "4", "--seq", "16", "--micro-batch", "1",
          "--steps", "3", "--num-experts", "8", "--opt-level", "O2"]),
        ("examples/bert_finetune.py",
         ["--tp", "2", "--vocab", "64", "--layers", "1",
          "--hidden", "32", "--heads", "2", "--seq", "16",
          "--batch", "1", "--steps", "3", "--eval-batches", "1"]),
    ],
)
def test_example_runs(script, args):
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "."
    out = subprocess.run(
        [sys.executable, script] + args,
        capture_output=True, text=True, timeout=500, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]


@pytest.mark.slow
def test_imagenet_trainer_checkpoint_roundtrip(tmp_path):
    """The flagship trainer's save/resume through apex_tpu.checkpoint
    round-trips the FULL training state bitwise (reference: the
    main_amp.py checkpoint dict — params + optimizer + epoch +
    best_prec1 — restored exactly by --resume)."""
    import importlib.util
    import os

    from apex_tpu import checkpoint
    from apex_tpu.transformer import parallel_state

    spec = importlib.util.spec_from_file_location(
        "imagenet_amp", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "examples", "imagenet_amp.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    ckdir = str(tmp_path / "ck")
    base = ["--depth", "18", "--batch-size", "1", "--image-size", "32",
            "--steps-per-epoch", "2", "--eval-steps", "1",
            "--num-classes", "10", "--checkpoint-dir", ckdir]
    try:
        out1 = mod.main(base + ["--epochs", "1"])
    finally:
        parallel_state.destroy_model_parallel()

    def assert_tree_equal(a, b, what):
        # tree_map fails loudly on structure mismatch (zip would
        # silently truncate)
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=what),
            a, b,
        )

    # the epoch-0 checkpoint holds exactly the state main() returned
    saved = checkpoint.restore_step(ckdir, step=0)
    for key in ("params", "opt_state", "bn_stats"):
        assert_tree_equal(saved[key], out1[key], key)
    assert int(saved["epoch"]) == 0
    assert float(saved["best_prec1"]) == out1["best_prec1"]

    # --resume with epochs=1 restores and immediately returns: the
    # returned state must be the checkpoint, bitwise
    try:
        out2 = mod.main(base + ["--epochs", "1", "--resume"])
    finally:
        parallel_state.destroy_model_parallel()
    assert_tree_equal(out1["params"], out2["params"], "resume params")
    assert out2["best_prec1"] == out1["best_prec1"]
