"""chip_smoke.py, small, on the CPU: the two phases at a tiny width with
the same checks the chip run makes (minus "the kernel is in the compiled
text" — off the TPU the dispatcher resolves to XLA), the script's
refusal of anything but a TPU, and the compile-cache helper's rule."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from apex_tpu.transformer import parallel_state
from apex_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Size(
    layers=2, hidden=128, heads=4, seq=64, vocab=512,
    slots=2, page_size=8, requests=4, new_tokens=4)


def test_train_then_serve_tiny(tmp_path):
    """Trainer at dp=4 x tp=2, server at tp=4 on the 8 virtual devices:
    falling finite loss, every request answered in range, identical
    second pass with no new jit entries, paged logits near
    model.apply — and the params re-placed across the two meshes."""
    clock = chip_smoke.CompileClock()
    vocab = TINY.padded_vocab(4)
    try:
        trained = chip_smoke.train(
            TINY, tp=2, vocab=vocab, clock=clock, on_tpu=False,
            metrics_jsonl=str(tmp_path / "train.jsonl"))
        assert len(trained["losses"]) == TINY.steps
        chip_smoke.check_spread(trained["params"], on_tpu=False)
        served = chip_smoke.serve(
            TINY, trained["model"], trained["params"], tp=4, clock=clock,
            on_tpu=False)
        chip_smoke.check_spread(served["params"], on_tpu=False)
    finally:
        parallel_state.destroy_model_parallel()
    assert sorted(served["streams"]) == list(range(TINY.requests))
    assert clock.total > 0


def test_layout_is_an_error_not_a_smaller_run():
    assert chip_smoke.layout(1, 16) == {
        "train_tp": 1, "train_dp": 1, "serve_tp": 1}
    assert chip_smoke.layout(4, 16) == {
        "train_tp": 2, "train_dp": 2, "serve_tp": 4}
    with pytest.raises(chip_smoke.SmokeFailure, match="3 devices"):
        chip_smoke.layout(3, 16)
    assert chip_smoke.GPT2_345M.padded_vocab(1) == 50304
    assert chip_smoke.GPT2_345M.padded_vocab(4) == 50688


def test_result_line_has_exactly_the_keys_the_driver_reads():
    """The driver refuses the PR on any other key (it refused one that
    carried the layout too)."""
    devices = jax.devices()
    got = json.loads(chip_smoke.result_line(devices))
    assert got == {"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}
    assert "\n" not in chip_smoke.result_line(devices)


def test_script_refuses_the_cpu():
    """No TPU in the sandbox: non-zero exit, the platform named, no
    result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert "JAX_PLATFORMS='cpu'" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)


def test_cache_helper_leaves_the_environments_directory_alone(
        monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.ensure_compilation_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_defaults_inside_the_checkout(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.ensure_compilation_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.cache_entries(
        os.path.join(REPO, "no-such-dir")) == 0
