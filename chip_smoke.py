"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of GPT-2 345M (24 layers, hidden 1024, 16 heads of 64,
learned positions, GELU, LayerNorm, sequence 1024, vocabulary 50257
padded by Megatron's rule to a multiple of 128 x tp), random weights
from a seed:

- **trains**: ``examples/gpt_pretrain.py:main`` for 6 steps (O5) on the
  seeded synthetic stream, its metrics stream written to
  ``chiprun_out/chip_smoke_train.jsonl`` (git-ignored) and the losses
  read back from there;
- **serves** the params the trainer returned: ``GPTModel.decode_fns`` ->
  ``PagedKVCache`` / ``init_pools`` -> ``ContinuousBatcher.run``, 16
  greedy requests on 8 slots, twice.

One process, which owns every chip it sees (a second process that needs
the chip would fail or hang).  It refuses any platform but ``tpu``,
catches nothing, and exits non-zero on any exception, any failed check,
any phase that did not run.  The last line of stdout is one JSON object
with exactly ``"ok"`` and ``"device"`` (platform, kind, count as jax
reports them) — the driver's check reads that line and refuses any
other key; the layout is printed on a line of its own above it.

    python chip_smoke.py

The phases are plain functions of a :class:`Size`, so
``tests/test_chip_smoke.py`` runs them small on the CPU; only running
this file as a script insists on a TPU.  The times it prints are plain
host-clock facts for the log, not metrics.
"""

import collections
import dataclasses
import importlib.metadata
import importlib.util
import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Size:
    """One model width plus the trainer's and the server's load."""

    layers: int
    hidden: int
    heads: int
    seq: int                # trained length == the learned table's end
    vocab: int              # unpadded
    micro_batch: int = 4
    num_micro: int = 2
    steps: int = 6
    slots: int = 8
    page_size: int = 64
    requests: int = 16
    new_tokens: int = 32

    @property
    def pages_per_seq(self) -> int:
        return self.seq // self.page_size

    @property
    def max_prompt(self) -> int:
        return self.seq - self.page_size

    def padded_vocab(self, tp: int) -> int:
        """Megatron's rule: the next multiple of 128 x tp."""
        unit = 128 * tp
        return -(-self.vocab // unit) * unit


GPT2_345M = Size(layers=24, hidden=1024, heads=16, seq=1024, vocab=50257)


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds jax spent obtaining executables (XLA compilation on a
    cold cache, a cache read on a warm one) and how many it obtained
    per jitted function, since this clock was made: a view of the
    program's own ledger (``apex_tpu.telemetry.programs``), which is
    the one listener to jax's compile events."""

    def __init__(self):
        from apex_tpu.telemetry import programs

        self._ledger = programs.ledger
        self._count0 = self._ledger.count
        self._total0 = self._ledger.obtain_s_total

    @property
    def total(self) -> float:
        return self._ledger.obtain_s_total - self._total0

    @property
    def times(self) -> collections.Counter:
        """"jit(name)" -> executables."""
        return collections.Counter(
            f"jit({r.name})"
            for r in self._ledger.records_from(self._count0))


def layout(n_devices: int, heads: int) -> dict:
    """Trainer tp = min(2, n), dp = n / tp; server tp = n.  Anything
    the heads do not divide into is an error, not a smaller run."""
    check(n_devices >= 1 and heads % n_devices == 0,
          f"{n_devices} devices do not divide {heads} heads")
    train_tp = min(2, n_devices)
    return {"train_tp": train_tp, "train_dp": n_devices // train_tp,
            "serve_tp": n_devices}


def result_line(devices) -> str:
    """The last line of stdout: exactly the keys the driver's check
    reads, the device as jax reports it.  Reached only when every phase
    passed (a failed check raised long before)."""
    return json.dumps({
        "ok": True,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices)},
    })


def peak_bytes() -> str:
    """Largest ``peak_bytes_in_use`` over the devices, where the
    backend reports one (the CPU does not)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None for s in stats):
        return "not reported"
    return f"{max(s['peak_bytes_in_use'] for s in stats):,}"


def _drop_mesh() -> None:
    """Each phase lays the devices out for itself."""
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()


def _compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


# ------------------------------------------------------------------ train
def train(size: Size, *, tp: int, vocab: int, clock: CompileClock,
          on_tpu: bool, metrics_jsonl: str) -> dict:
    """A few steps through ``examples/gpt_pretrain.py:main``, the
    per-step losses read back from the trainer's own ``--metrics-jsonl``
    sink.  Returns what ``main`` returned (params, model, jitted step)
    plus ``losses``."""
    spec = importlib.util.spec_from_file_location(
        "gpt_pretrain", os.path.join(REPO, "examples", "gpt_pretrain.py"))
    trainer = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trainer)

    _drop_mesh()  # main builds its own
    if os.path.exists(metrics_jsonl):
        os.remove(metrics_jsonl)  # the sink appends
    c0, t0 = clock.total, time.perf_counter()
    out = trainer.main([
        "--tp", str(tp), "--vocab", str(vocab),
        "--layers", str(size.layers), "--hidden", str(size.hidden),
        "--heads", str(size.heads), "--seq", str(size.seq),
        "--opt-level", "O5", "--micro-batch", str(size.micro_batch),
        "--num-micro", str(size.num_micro), "--steps", str(size.steps),
        "--log-every", "1", "--metrics-jsonl", metrics_jsonl,
    ])
    wall = time.perf_counter() - t0
    with open(metrics_jsonl) as f:
        records = [json.loads(line) for line in f]
    losses = [r["loss"] for r in records if r["kind"] == "step"]
    out["losses"] = losses
    print(f"train: losses {' '.join(f'{x:.4f}' for x in losses)}")
    print(f"train: wall {wall:.1f} s, compile {clock.total - c0:.1f} s, "
          f"steady {out['summary']['ms_per_step']:.1f} ms/step over "
          f"{out['summary']['timed_steps']} steps, "
          f"peak_bytes_in_use {peak_bytes()}")
    check(len(losses) == size.steps, f"{len(losses)} of {size.steps} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    check(clock.times["jit(train_step)"] == 1,
          f"train_step compiled {clock.times['jit(train_step)']} times")
    if on_tpu:
        # attention ran as a Mosaic kernel, not as XLA
        check("tpu_custom_call" in _compiled_text(
            out["step"], *out["step_args"]),
            "no tpu_custom_call in the compiled train step")
        print("train: tpu_custom_call present in the compiled step")
    return out


# ------------------------------------------------------------------ serve
def _requests(size: Size, seed: int):
    """Seeded prompts whose lengths spread over one page .. max_prompt."""
    from apex_tpu.serving.serve import Request

    rng = np.random.default_rng(seed)
    lengths = rng.permutation(np.linspace(
        size.page_size, size.max_prompt, size.requests).astype(int))
    return [Request(uid=i, max_new_tokens=size.new_tokens,
                    prompt=[int(t) for t in
                            rng.integers(0, size.vocab, int(n))])
            for i, n in enumerate(lengths)]


def _paged_vs_apply(model, fns, params, mesh, ccfg, pools,
                    prompt) -> tuple:
    """One prompt through the SERVED path — the compiled prefill writes
    its first L-1 tokens into the paged cache, one decode step reads
    them back through the decode kernel — against ``model.apply`` (the
    training forward) on the same L tokens.  Returns (max |diff| of the
    last position's logits, max |logit|)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.serving.kv_cache import PagedKVCache
    from apex_tpu.transformer.tensor_parallel import (
        gather_from_tensor_model_parallel_region as gather,
    )

    L = len(prompt)
    cache = PagedKVCache(ccfg)
    cache.admit(0, L)
    padded = np.zeros((1, ccfg.max_len - ccfg.page_size), np.int32)
    padded[0, :L] = prompt
    pools, _ = fns.prefill(
        pools, jnp.asarray(padded), jnp.int32(L - 1),
        jnp.asarray(cache.page_table[0]), jax.random.PRNGKey(0))

    slot0 = np.arange(ccfg.max_seqs) == 0

    def decode_logits(p, pools, tok, pos, pt):
        logits, _ = model.decode_step(
            p, tok, pos, jnp.asarray(slot0), pt, pools)
        return gather(logits)[0]

    def apply_logits(p, toks):
        return gather(model.apply(p, toks))[0, L - 1]

    served = jax.jit(jax.shard_map(
        decode_logits, mesh=mesh,
        in_specs=(fns.param_specs, fns.pool_specs, P(), P(), P()),
        out_specs=P()))(
        params, pools,
        jnp.where(slot0, prompt[L - 1], 0).astype(jnp.int32),
        jnp.where(slot0, L - 1, 0).astype(jnp.int32),
        jnp.asarray(cache.page_table))
    ref = jax.jit(jax.shard_map(
        apply_logits, mesh=mesh, in_specs=(fns.param_specs, P()),
        out_specs=P()))(params, jnp.asarray(padded[:, :L]))
    served = np.asarray(served, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(served - ref))), float(np.max(np.abs(ref)))


def serve(size: Size, model, params, *, tp: int, clock: CompileClock,
          on_tpu: bool) -> dict:
    """Serve ``params`` through decode_fns -> PagedKVCache/init_pools ->
    ContinuousBatcher.run, twice over the same request list."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.serving.kv_cache import (
        KVCacheConfig, PagedKVCache, init_pools,
    )
    from apex_tpu.serving.serve import ContinuousBatcher
    from apex_tpu.transformer import parallel_state

    _drop_mesh()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=tp)
    ccfg = KVCacheConfig(
        num_layers=size.layers, num_heads=size.heads,
        head_dim=size.hidden // size.heads,
        num_pages=1 + size.slots * size.pages_per_seq,
        page_size=size.page_size, max_seqs=size.slots,
        pages_per_seq=size.pages_per_seq, dtype=jnp.bfloat16)
    c0, t0 = clock.total, time.perf_counter()
    place = lambda tree, specs: jax.device_put(tree, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P)))
    # the trainer's layout -> the server's: same leaves, another mesh
    params = place(params, model.param_specs())
    fns = model.decode_fns(params, mesh, ccfg,
                           max_prompt_len=size.max_prompt,
                           weight_dtype=None)
    place_pools = lambda: place(init_pools(ccfg), fns.pool_specs)
    reqs = _requests(size, seed=0)

    def one_pass():
        batcher = ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(ccfg), place_pools(),
            max_prompt_len=size.max_prompt)
        t = time.perf_counter()
        done = batcher.run(reqs)
        return ({uid: list(c.tokens) for uid, c in done.items()},
                time.perf_counter() - t, batcher)

    # dispatch-cache entries: one per spelling of the argument
    # shardings (a placed pool is P(None, None, "tp", None, None), the
    # same pool returned by a step P(None, None, "tp") or P()), so the
    # first pass may leave two per step; executables are counted apart
    jit_entries = lambda: (fns.prefill_jit._cache_size(),
                           fns.decode_jit._cache_size())
    first, wall1, _ = one_pass()
    compile_s = clock.total - c0
    jits = jit_entries()
    compiled = (clock.times["jit(_prefill)"], clock.times["jit(_decode)"])
    second, wall2, batcher = one_pass()
    n_tok = sum(len(t) for t in second.values())
    print(f"serve: {len(first)} requests on {size.slots} slots, "
          f"{size.new_tokens} new tokens each; first pass {wall1:.1f} s "
          f"(compile {compile_s:.1f} s), second pass "
          f"{wall2 * 1e3 / n_tok:.2f} ms/token over {n_tok} tokens; "
          f"executables prefill/decode {compiled}, dispatch-cache "
          f"entries {jits}, peak_bytes_in_use {peak_bytes()}")
    check(compiled == (1, 1),
          f"prefill/decode compiled {compiled} times in the first pass")
    check(len(first) == size.requests,
          f"{len(first)} of {size.requests} requests completed")
    for uid, toks in first.items():
        check(len(toks) == size.new_tokens,
              f"request {uid}: {len(toks)} tokens")
        # the unpadded vocabulary: a pad id is not a token
        check(all(0 <= t < size.vocab for t in toks),
              f"request {uid}: token outside the vocabulary")
    check(first == second, "the second pass gave different streams")
    check(jit_entries() == jits and compiled == (
        clock.times["jit(_prefill)"], clock.times["jit(_decode)"]),
        "the second pass recompiled a serving step")
    if on_tpu:
        # fmha_decode ran as a Mosaic kernel, not as XLA
        check("tpu_custom_call" in _compiled_text(
            fns.decode_jit, params, batcher.pools, batcher.carry,
            jnp.asarray(batcher.cache.page_table)),
            "no tpu_custom_call in the compiled decode step")
        print("serve: tpu_custom_call present in the compiled decode step")
    diff, scale = _paged_vs_apply(
        model, fns, params, mesh, ccfg, place_pools(),
        reqs[0].prompt[:size.max_prompt // 2])
    print(f"serve: paged prefill+decode vs model.apply, last-position "
          f"logits: max |diff| {diff:.4f} (max |logit| {scale:.3f}), "
          f"total wall {time.perf_counter() - t0:.1f} s")
    # the two paths agree to one bf16 ulp of the largest logit on the
    # v5e (0.0156 at 3.6); the bound is about four, so a lower-precision
    # cache or compute path fails it like a wrong page or position does
    check(math.isfinite(diff) and diff <= 0.02 * max(scale, 1.0),
          f"paged logits differ from model.apply by {diff}")
    return {"params": params, "streams": first}


def check_spread(params, on_tpu: bool) -> None:
    """More than one chip: every parameter lives on all of them, and
    each holds bytes."""
    import jax

    devices = jax.devices()
    if len(devices) == 1:
        return
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        check(leaf.sharding.device_set == set(devices),
              f"{jax.tree_util.keystr(path)} is on "
              f"{len(leaf.sharding.device_set)} of {len(devices)} devices")
    if on_tpu:
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        print(f"bytes_in_use per device: {in_use}")
        check(all(b > 0 for b in in_use), f"an idle device: {in_use}")


# ------------------------------------------------------------------- main
def main() -> int:
    import apex_tpu
    from apex_tpu.utils.compile_cache import (
        cache_entries, ensure_compilation_cache,
    )

    # this checkout's program, not one that happens to be importable
    check(os.path.dirname(os.path.abspath(apex_tpu.__file__))
          == os.path.join(REPO, "apex_tpu"),
          f"apex_tpu came from {apex_tpu.__file__}, not from {REPO}")
    cache_dir = ensure_compilation_cache()  # before the backend starts
    entries_before = cache_entries(cache_dir)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found platform {platform!r} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
              file=sys.stderr)
        return 1

    from apex_tpu.telemetry.metrics import device_peak_flops

    size = GPT2_345M
    plan = layout(len(devices), size.heads)
    vocab = size.padded_vocab(plan["serve_tp"])
    versions = {d.metadata["Name"]: d.version
                for d in importlib.metadata.distributions()
                if d.metadata["Name"] in ("jax", "jaxlib", "libtpu")}
    print(f"platform {platform}, device_kind {devices[0].device_kind}, "
          f"{len(devices)} device(s), peak "
          f"{device_peak_flops(devices[0]):.3g} FLOP/s bf16; {versions}")
    print(f"layout {plan}, vocabulary {size.vocab} padded to {vocab}")
    print(f"compile cache {cache_dir}: {entries_before} entries")

    clock = CompileClock()
    trained = train(size, tp=plan["train_tp"], vocab=vocab, clock=clock,
                    on_tpu=True, metrics_jsonl=os.path.join(
                        REPO, "chiprun_out", "chip_smoke_train.jsonl"))
    check_spread(trained["params"], on_tpu=True)
    served = serve(size, trained["model"], trained["params"],
                   tp=plan["serve_tp"], clock=clock, on_tpu=True)
    check_spread(served["params"], on_tpu=True)

    entries_after = cache_entries(cache_dir)
    print(f"compile cache {cache_dir}: {entries_after} entries "
          f"(+{entries_after - entries_before}), compile "
          f"{clock.total:.1f} s in all")
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
