"""What the state-space half of a Falcon-H1 layer needs at the least,
beside ``rooflines.py`` (whose peaks and ``least_seconds`` these counts
are set against); its grouped-query page walk is counted by
``rooflines_window_moe.full_decode``, from the rows the step's own
counters say it read.

Each count is what the ALGORITHM needs, whatever implements it: nothing
padded, nothing recomputed, every operand read once and every result
written once.  A share above 100 % means a count here is too high or
the time leaves out part of the work; it is never clipped.
"""

from __future__ import annotations

from typing import Tuple

F32 = 4.0


def ssm_state_update(counters: dict, config: dict) -> Tuple[float, float]:
    """The decode step's state updates, every layer: each live slot's
    recurrent state read and written once (``ssm_state_bytes_per_step``,
    the step's own count); per state element a decay, a product and a
    sum for the new state and a product and a sum for ``y``."""
    nbytes = counters["ssm_state_bytes_per_step"]
    elements = counters["live_slot_layers_per_step"] * config[
        "mamba_n_heads"] * config["mamba_d_head"] * config["mamba_d_state"]
    return 5.0 * elements, nbytes


def ssd_chunk_scan(counters: dict, config: dict) -> Tuple[float, float]:
    """One prefill chunk's SSD scans, every layer, ``chunk_tokens``
    tokens in blocks of ``mamba_chunk_size``: per block the ``C B^T``
    product (per group), its decay-weighted product with x (per head),
    the block's contribution to the state and the entering state's
    product with C; in float32: x, dt, B, C in, y out, the state in and
    out."""
    T = counters["chunk_tokens"]
    L = config["mamba_chunk_size"]
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    layers = config["num_hidden_layers"]
    flops = 2.0 * T * L * (G * N + H * P) + 2.0 * 2.0 * T * H * P * N
    nbytes = F32 * (2.0 * T * H * P + T * H + 2.0 * T * G * N
                    + 2.0 * H * P * N)
    return layers * flops, layers * nbytes


KERNELS = {"ssm_state_update": ssm_state_update,
           "ssd_chunk_scan": ssd_chunk_scan}
