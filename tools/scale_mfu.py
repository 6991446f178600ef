"""SCALE_MFU: MFU vs model scale on the real chip.

PROFILE_r05's roofline argument says the flagship's MFU ceiling
(~0.51 at 185M params / h1024) is a property of the model SCALE — the
h=1024 contraction dims cap single-matmul MXU efficiency near 60% on
v5e — and that the 0.55 target falls out at larger hidden sizes, not
from further tuning at h1024.  This tool measures that claim directly:
the same train step (bf16 + fp32 masters + FusedAdam + remat + flash
attention + auto-CE — byte-for-byte the bench flagship program, only
the config scaled) at increasing hidden size on one chip.

Writes SCALE_MFU.json.  Run (chip required): python tools/scale_mfu.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEQ = 1024
WARMUP, STEPS = 2, 10

# (layers, hidden, heads, batch): hidden doubles while the optimizer
# state stays inside v5e HBM (16 GB): h2048/12L is ~671M params
# -> ~9.4 GB of bf16 params + fp32 masters + moments
CONFIGS = [
    ("flagship_h1024", 12, 1024, 8, 8),
    ("h1536", 12, 1536, 12, 8),
    ("h2048", 12, 2048, 16, 8),
]


def measure(tag, layers, hidden, heads, batch):
    from bench import FLAGSHIP, _peak_flops
    from apex_tpu.telemetry.metrics import transformer_flops_per_token
    from tools.profile_r05 import build

    params, opt_state, step, n_params = build(
        num_layers=layers, hidden_size=hidden, num_attention_heads=heads,
    )
    vocab = FLAGSHIP["vocab_size"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, SEQ), 0, vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    for _ in range(WARMUP):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    final = float(loss)
    dt = (time.perf_counter() - t0) / STEPS
    assert jnp.isfinite(final), f"{tag}: non-finite loss"
    # the shared model-FLOP estimate (6N + 12*L*h*s) — the same
    # numerator the live telemetry's StepStats MFU uses
    flops_per_token = transformer_flops_per_token(
        n_params, layers, hidden, SEQ)
    tok_s = batch * SEQ / dt
    peak = _peak_flops(jax.devices()[0])
    mfu = tok_s * flops_per_token / peak if peak else None
    row = {
        "tag": tag, "layers": layers, "hidden": hidden, "heads": heads,
        "batch": batch, "seq": SEQ, "n_params": n_params,
        "ms_per_step": round(dt * 1e3, 2),
        "tokens_per_sec": round(tok_s, 1),
        "mfu": round(mfu, 4) if mfu else None,
    }
    print(json.dumps(row), flush=True)
    return row


def main():
    plat = jax.devices()[0].platform
    if plat != "tpu":
        raise SystemExit(f"scale_mfu must run on TPU (got {plat})")
    rows = []
    for cfg in CONFIGS:
        try:
            rows.append(measure(*cfg))
        except AssertionError:
            raise  # non-finite loss is a correctness failure, never OOM
        except Exception as e:
            # OOM at the largest config is a finding, not a failure —
            # keep every completed row of a scarce chip session
            rows.append({"tag": cfg[0], "error": str(e)[:300]})
            print(f"{cfg[0]}: FAILED ({str(e)[:160]})", flush=True)
    doc = {
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "note": (
            "same train-step program as the bench flagship (build() from "
            "tools/profile_r05.py), hidden size scaled; PROFILE_r05's "
            "roofline predicts MFU rises with hidden because h=1024 "
            "contraction dims bound MXU efficiency, not any missing "
            "optimization"
        ),
        "rows": rows,
    }
    with open(os.path.join(REPO, "SCALE_MFU.json"), "w") as f:
        json.dump(doc, f, indent=1)
    print("wrote SCALE_MFU.json")


if __name__ == "__main__":
    main()
