"""The table of peaks, and what each kernel has to do at the least.

The yardstick lives here, under the benchmark's own directory, so that a
PR which claims a gain cannot move it.  A kernel's roofline share is

    max(operations / peak FLOP/s, bytes / peak bytes/s) / measured time

with operations and bytes counted from the shapes the ALGORITHM needs
(not the padded operands the kernel happens to be handed, and nothing
recomputed).  A share above 100 % means this file counts too much or the
time leaves out part of the work; it is never clipped.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: per chip, keyed by ``device_kind`` as jax reports it.  Source: Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s, 16 GB HBM.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """A device that is not in the table is an error, not a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"add a row to benchmarks/rooflines.py with its source")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    p = peaks(device_kind)
    return max(flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])


# ------------------------------------------------------ model arithmetic
def parameter_count(cfg: dict, vocab_rows: int) -> int:
    """Parameters of the GPT-2 block stack with a tied head: embedding
    rows as held (padded), learned positions, per layer qkv + proj +
    two MLP matrices with biases and two LayerNorms, and a final one."""
    h, f, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    per_layer = (h * 3 * h + 3 * h) + (h * h + h) + (h * f + f) \
        + (f * h + h) + 4 * h
    return vocab_rows * h + cfg["n_positions"] * h + L * per_layer + 2 * h


def train_flops_per_token(cfg: dict, vocab_rows: int, seq: int) -> int:
    """Required model FLOPs per trained token: 6 N for the matrix
    multiplications forward and backward plus 12 L h s for attention
    scores and context (the trainer's own ``transformer_flops_per_token``
    copied, so that the yardstick does not move with the program).
    Recomputation under remat is NOT counted."""
    n = parameter_count(cfg, vocab_rows)
    return 6 * n + 12 * cfg["n_layer"] * cfg["n_embd"] * seq


# ------------------------------------------------------- kernel minimums
def train_attention(kind: str, batch: int, heads: int, seq: int,
                    head_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) one causal attention call needs, per chip.
    Forward: QK^T and PV, half of each masked away: 2 b h s^2 d.
    Backward: five such products (scores again, dV, dP, dQ, dK):
    5 b h s^2 d.  Bytes: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv (the log-sum-exp rows are
    small beside them and left out)."""
    unit = batch * heads * seq * seq * head_dim
    tensor = batch * heads * seq * head_dim * itemsize
    if kind == "forward":
        return 2.0 * unit, 4.0 * tensor
    if kind == "backward":
        return 5.0 * unit, 8.0 * tensor
    raise ValueError(kind)


def decode_attention(live_context_tokens: float, heads: int, head_dim: int,
                     itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode-attention call (one layer, one step)
    needs: every live sequence's K and V read once, 4 FLOPs per cached
    element pair.  ``live_context_tokens`` is the sum of the live
    sequences' context lengths at that step."""
    elems = live_context_tokens * heads * head_dim
    return 4.0 * elems, 2.0 * elems * itemsize
