"""Multi-process launcher for multi-host SPMD runs.

Capability match of ``python -m apex.parallel.multiproc``
(reference: apex/parallel/multiproc.py:1-35 — the pre-torchrun
one-process-per-GPU local launcher).  On TPU, multi-host JAX uses one
process per host with ``jax.distributed.initialize``; this launcher
spawns N local processes wired together through a local coordinator so
the multi-host code path (process_index/process_count, cross-host
collectives over DCN) can be exercised on a single machine::

    python -m apex_tpu.parallel.multiproc --nprocs 2 train.py --args...

Each child gets APEX_TPU_PROCESS_ID / APEX_TPU_NUM_PROCESSES /
APEX_TPU_COORDINATOR env vars; call :func:`initialize_distributed` at
the top of the script to join the cluster (the analog of the
reference's ``initialize_distributed`` env-var recipe,
apex/transformer/testing/commons.py:81-113).

This is a multi-HOST launcher.  On a host with local TPU chips ONE
process drives all of them: every child would see every local chip, the
first to start takes them, and the second fails or hangs.  So
``--nprocs > 1`` is refused there unless the caller has divided the
chips itself (``TPU_VISIBLE_CHIPS`` and friends) or pinned the children
off the TPU (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import argparse
import glob
import os
import subprocess
import sys

__all__ = ["initialize_distributed", "main"]


def initialize_distributed() -> None:
    """Join the process group described by the launcher's env vars (or
    no-op when running single-process)."""
    nproc = int(os.environ.get("APEX_TPU_NUM_PROCESSES", "1"))
    if nproc <= 1:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=os.environ["APEX_TPU_COORDINATOR"],
        num_processes=nproc,
        process_id=int(os.environ["APEX_TPU_PROCESS_ID"]),
    )


#: libtpu's chip-visibility variables: a caller that sets one has
#: divided the local chips between the processes itself
_CHIP_VISIBILITY_VARS = (
    "TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES",
    "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
)


def _local_tpu_chips() -> list:
    """Device nodes of this host's TPU chips — found WITHOUT importing
    jax, which would make the launcher itself take the chips."""
    return sorted(glob.glob("/dev/accel[0-9]*")
                  + glob.glob("/dev/vfio/[0-9]*"))


def _refuse_shared_chips(nprocs: int) -> None:
    if nprocs <= 1:
        return
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return
    if any(os.environ.get(v) for v in _CHIP_VISIBILITY_VARS):
        return
    chips = _local_tpu_chips()
    if chips:
        raise SystemExit(
            f"multiproc: --nprocs {nprocs} on a host with local TPU "
            f"chips ({', '.join(chips)}): one process drives all local "
            "chips, so a second process that sees them fails or hangs. "
            "Run the script directly, or divide the chips yourself "
            f"({' / '.join(_CHIP_VISIBILITY_VARS[:2])}), or pin the "
            "children off the TPU with JAX_PLATFORMS=cpu.")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="spawn N local processes for multi-host-style SPMD"
    )
    ap.add_argument("--nprocs", type=int, default=2)
    ap.add_argument("--port", type=int, default=12355)
    ap.add_argument("script", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.script:
        ap.error("no script given")
    _refuse_shared_chips(args.nprocs)

    procs = []
    for rank in range(args.nprocs):
        env = dict(os.environ)
        env["APEX_TPU_PROCESS_ID"] = str(rank)
        env["APEX_TPU_NUM_PROCESSES"] = str(args.nprocs)
        env["APEX_TPU_COORDINATOR"] = f"127.0.0.1:{args.port}"
        procs.append(
            subprocess.Popen([sys.executable] + args.script, env=env)
        )
    rc = 0
    for p in procs:
        rc = rc or p.wait()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
