"""Small parity modules: multiproc launcher, memory buffers, autocast."""

import subprocess
import sys

import numpy as np
import jax.numpy as jnp

from apex_tpu._autocast_utils import (
    _cast_if_autocast_enabled,
    autocast,
    get_autocast_dtype,
)
from apex_tpu.transformer.tensor_parallel.memory import (
    GlobalMemoryBuffer,
    RingMemBuffer,
)


def test_autocast_context():
    assert get_autocast_dtype() is None
    x = jnp.ones(3, jnp.float32)
    i = jnp.arange(3)
    assert _cast_if_autocast_enabled(x)[0].dtype == jnp.float32
    with autocast(jnp.bfloat16):
        cx, ci = _cast_if_autocast_enabled(x, i)
        assert cx.dtype == jnp.bfloat16 and ci.dtype == jnp.int32
        with autocast(enabled=False):
            assert _cast_if_autocast_enabled(x)[0].dtype == jnp.float32
        assert get_autocast_dtype() == jnp.bfloat16
    assert get_autocast_dtype() is None


def test_global_memory_buffer_reuses():
    buf = GlobalMemoryBuffer()
    a = buf.get_tensor((4, 4), np.float32, "x")
    b = buf.get_tensor((4, 4), np.float32, "x")
    assert a is b
    c = buf.get_tensor((4, 4), np.float32, "y")
    assert c is not a


def test_ring_buffer_cycles():
    ring = RingMemBuffer("r", 3, (2,), np.float32)
    bufs = [ring.get_next_buffer() for _ in range(4)]
    assert bufs[0] is bufs[3]
    assert bufs[0] is not bufs[1]


def test_multiproc_launcher_wires_env(tmp_path):
    child = tmp_path / "child.py"
    child.write_text(
        "import os\n"
        "print(os.environ['APEX_TPU_PROCESS_ID'],"
        " os.environ['APEX_TPU_NUM_PROCESSES'])\n"
    )
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.parallel.multiproc",
         "--nprocs", "2", str(child)],
        capture_output=True, text=True, timeout=120,
        env={"PYTHONPATH": ".", "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )
    assert out.returncode == 0, out.stderr
    lines = sorted(out.stdout.strip().splitlines())
    assert lines == ["0 2", "1 2"]


def test_multiproc_refuses_to_share_local_chips(monkeypatch):
    """On a host with local TPU chips one process drives them all: the
    launcher refuses --nprocs > 1 unless the caller divided the chips
    or pinned the children off the TPU."""
    import pytest

    from apex_tpu.parallel import multiproc

    monkeypatch.setattr(multiproc, "_local_tpu_chips",
                        lambda: ["/dev/accel0", "/dev/accel1"])
    for var in multiproc._CHIP_VISIBILITY_VARS + ("JAX_PLATFORMS",):
        monkeypatch.delenv(var, raising=False)
    with pytest.raises(SystemExit, match="one process drives all"):
        multiproc._refuse_shared_chips(2)
    multiproc._refuse_shared_chips(1)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    multiproc._refuse_shared_chips(2)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    multiproc._refuse_shared_chips(2)


def test_platform_detection_tracks_backend(monkeypatch):
    """A mid-process backend switch must not leave is_tpu() stale
    (the situation __graft_entry__._force_cpu_platform creates)."""
    from apex_tpu.utils import platform as plat

    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.setattr(plat, "_current_platform", lambda: "tpu")
    assert plat.is_tpu()
    assert plat.default_implementation() == "pallas"
    # flip the backend mid-process: detection must follow, no reset needed
    monkeypatch.setattr(plat, "_current_platform", lambda: "cpu")
    assert not plat.is_tpu()
    assert plat.default_implementation() == "xla"
    # env override is honored per call, not cached
    monkeypatch.setattr(plat, "_current_platform", lambda: "tpu")
    monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
    assert plat.is_tpu() and not plat.supports_pallas()
    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS")
    assert plat.supports_pallas()
