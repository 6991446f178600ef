"""What the latent-attention, sparse-selection and held-expert work of
ONE DECODE STEP needs at the least, beside ``rooflines.py`` (whose peaks
and ``least_seconds`` these counts are set against).

Each count is what the ALGORITHM needs, from the step's own counters
(``benchmarks/runners/serve_latent_moe.py:derived_counters``), whether
the work is a Mosaic kernel or a group of fusions under a scope; nothing
padded, nothing recomputed.  A share above 100 % means a count here is
too high or the time leaves out part of the work; it is never clipped.
"""

from __future__ import annotations

from typing import Tuple


def index_score(counters: dict, config: dict) -> Tuple[float, float]:
    """Index scores of a decode step, all layers: every live context
    token's index key read once (``index_head_dim`` x 2 B) and scored by
    ``index_n_heads`` heads (2 FLOPs a key element a head).
    ``index_context_tokens_per_step`` already sums slots AND layers."""
    tokens = counters["index_context_tokens_per_step"]
    d, heads = config["index_head_dim"], config["index_n_heads"]
    return tokens * heads * d * 2.0, tokens * d * 2.0


def mla_decode(counters: dict, config: dict) -> Tuple[float, float]:
    """Absorbed attention of a decode step over its gathered rows, all
    layers: each selected row (``kv_lora_rank + qk_rope_head_dim`` x 2 B)
    read once for all heads; per row and head a score over the whole row
    and a weighted sum over the latent part."""
    rows = counters["mla_rows_per_step"]
    latent = config["kv_lora_rank"]
    row = latent + config["qk_rope_head_dim"]
    heads = config["num_attention_heads"]
    return rows * heads * (row + latent) * 2.0, rows * row * 2.0


def moe_experts(counters: dict, config: dict) -> Tuple[float, float]:
    """The routed experts of a decode step, all expert layers: every
    DISTINCT touched expert's three matrices read once, 6 x hidden x
    width FLOPs for each (token, expert) choice that landed here."""
    per_expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    return (counters["held_choices_per_step"] * 2.0 * per_expert,
            counters["experts_touched_per_step"] * per_expert * 2.0)


KERNELS = {"index_score": index_score, "mla_decode": mla_decode,
           "moe_experts": moe_experts}
