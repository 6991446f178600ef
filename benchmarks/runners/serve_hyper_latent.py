"""Runner for ``backlog`` traffic on a model that serves from a paged
LATENT cache read whole, with several residual streams and every routed
expert held (Xing4.0, six of its layers, each whole on the chip).

The served path is the program's own: ``Xing4Model.decode_fns`` ->
``PagedKVCache`` / ``init_pools`` -> ``ContinuousBatcher.pump`` with
chunked prefill.  The queue, the clock, the books and the rate are
``runners.serve.Driver``'s; the order of every generation
(``FileOrderBacklog``), the decode steps' counters (``CountingDriver``,
``derived_counters``), the plan of the check (``check_plan``) and the
read of the served side (``served_check``) are
``runners.serve_latent_moe``'s, imported: the model keeps that model's
counter names and carry, its selection empty.  This file adds the
model's build, the comparison with ``reference/xing4.py`` and the chunk
programs' compiled texts.

``correct`` is decided in set-up, at the published widths, on what the
TIMED programs themselves computed with every slot occupied: one prompt
of at least 12,288 tokens is served twice, as the first and as the last
request of a batcher whose other slots hold short prompts of their own,
one decode step a ``pump``.  The chunk program hands back the logits of
the prompt's last position, the decode program leaves each step's
logits in its carry; both are held to the reference's full forward on
the prompt and the tokens the server generated after it, with the same
weights (``judge``): each position's largest logit error as a share of
the reference's largest |logit|, the median over the positions
(``logit_tolerance``: a path that computes in fewer bits, or mixes its
streams otherwise, moves EVERY position) and every single position, the
two chunk positions among them (``logit_tolerance_single``: what hits
one step, one page or one of the two forms of the attention).
"""

from __future__ import annotations

import math
import time

import numpy as np

import timing
import traffic as traffic_gen
from reference import xing4 as reference
from runners.serve_latent_moe import (
    CountingDriver, FileOrderBacklog, derived_counters, make_batcher,
    served_check,
)


# ------------------------------------------------------------ the build
def build(run):
    """The model on the device from the cell's files (the weights from
    the configuration's ``weights_seed``): (model, params, cache config,
    step functions, pool maker)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    try:
        from apex_tpu.models.xing4 import Xing4Config, Xing4Model
    except ImportError as e:
        raise SystemExit(
            f"runners/serve_hyper_latent.py: this checkout's program has "
            f"no such model: no hyper-connection latent-attention model "
            f"({e})")
    from apex_tpu.serving.kv_cache import KVCacheConfig, init_pools
    from apex_tpu.transformer import parallel_state

    cfg, tr = run.config, run.traffic
    slots, pages_per_seq = int(tr["slots"]), int(tr["pages_per_seq"])
    with run.phase("weights_on_device"):
        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=1)
        mcfg = Xing4Config.from_hf(cfg, params_dtype=jnp.bfloat16)
        model = Xing4Model(mcfg)
        on_mesh = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        # one jitted call, in the type they are served in; the
        # generator's own bits ("rbg"), as the latent cell's
        params = jax.jit(model.init, out_shardings=on_mesh(
            model.param_specs()))(jax.random.key(
                int(cfg["weights_seed"]), impl="rbg"))
        jax.block_until_ready(params)
    with run.phase("steps_and_pool"):
        ccfg = KVCacheConfig(
            num_layers=mcfg.num_hidden_layers, num_heads=1,
            head_dim=mcfg.latent_dim, num_pages=1 + slots * pages_per_seq,
            page_size=int(tr["page_size"]), max_seqs=slots,
            pages_per_seq=pages_per_seq, dtype=jnp.bfloat16, kind="latent",
            latent_dim=mcfg.latent_dim, index_dim=0)
        fns = model.decode_fns(
            params, mesh, ccfg, max_prompt_len=int(tr["max_prompt_len"]),
            prefill_chunk=int(tr["prefill_chunk"]))
        make_pools = jax.jit(lambda: init_pools(ccfg),
                             out_shardings=on_mesh(fns.pool_specs))
    return model, params, ccfg, fns, make_pools


# ------------------------------------------------------------ the check
def reference_prompt(run, params, sequence, n: int):
    """The reference's full forward on ``sequence``: the logits of the
    positions from ``n - 1`` on."""
    import jax

    jitted = {}

    def wrap(piece):
        # one call at a time (``serve_latent_moe.reference_prompt``
        # says why): a call's buffers are allocated when it is enqueued
        if piece not in jitted:
            compiled = jax.jit(piece, static_argnums=(
                reference.STATIC_ARGNUMS.get(piece.__name__, ())))
            jitted[piece] = lambda *args: jax.block_until_ready(
                compiled(*args))
        return jitted[piece]

    return np.asarray(reference.forward(
        params, np.asarray(sequence), reference.from_hf(run.config),
        positions=range(n - 1, len(sequence)),
        q_block=int(run.traffic["reference_q_block"]), wrap=wrap),
        np.float32)


def compare(served, n: int, ref_logits):
    """(every decode position's largest logit error as a share of the
    reference's largest |logit|; the same for each chunk position; that
    |logit|).  ``ref_logits`` start at position ``n - 1``."""
    scale = float(np.max(np.abs(ref_logits)))
    error = lambda got, at: float(
        np.max(np.abs(got - ref_logits[at - (n - 1)])) / scale)
    errors, chunk_errors = [], []
    for s in served:
        chunk_errors.append(error(s["chunk_logits"], s["chunk_at"]))
        errors += [error(s["logits"][step], at)
                   for step, at in enumerate(s["at"])]
    return errors, chunk_errors, scale


def judge(traffic: dict, errors, chunk_errors):
    """(why not correct: a list, empty when correct; the numbers that
    were compared): the median over all positions and every single
    one."""
    tolerance = float(traffic["logit_tolerance"])
    single = float(traffic["logit_tolerance_single"])
    numbers = {
        "logits_check_ratio": float(np.median(errors + chunk_errors)),
        "logits_check_ratio_max": max(errors + chunk_errors),
        "logits_check_ratio_chunk_max": max(chunk_errors),
    }
    why = []
    if not numbers["logits_check_ratio"] <= tolerance:
        why.append(f"served logits differ from the reference by "
                   f"{numbers['logits_check_ratio']} of the largest logit "
                   f"(median over positions; tolerance {tolerance})")
    if not numbers["logits_check_ratio_max"] <= single:
        why.append(f"at one position the served logits differ from the "
                   f"reference by {numbers['logits_check_ratio_max']} of "
                   f"the largest logit (chunk positions "
                   f"{[round(e, 4) for e in chunk_errors]}; tolerance for "
                   f"a single position {single})")
    return why, numbers


def verdict(run, params, slots: int, prompt, tokens, served, fewest_live):
    """What ``served_check`` returned against the reference's forward
    with ``params``: (why not correct, the numbers compared)."""
    n = len(prompt)
    why = []
    for s in served:
        if s["tokens"] != tokens[:len(s["tokens"])]:
            why.append(f"one prompt served in two slots gave different "
                       f"tokens: {s['name']} {s['tokens']} against {tokens}")
    if fewest_live < slots:
        why.append(f"only {fewest_live} of {slots} slots were live during "
                   f"the checked decode steps")
    errors, chunk_errors, scale = compare(
        served, n, reference_prompt(run, params, prompt + tokens[:-1], n))
    more, numbers = judge(run.traffic, errors, chunk_errors)
    run.note(
        f"reference: a prompt of {n} tokens served as the first and the "
        f"last of {slots} requests (chunked prefill, then "
        f"{[len(s['at']) for s in served]} paged decode steps with "
        f"{fewest_live} slots live at the least) vs the float32 reference "
        f"on {n + len(tokens) - 1} tokens; logits, share of max |logit| "
        f"{scale:.3f}: chunk positions "
        f"{[round(e, 4) for e in chunk_errors]}, decode positions median "
        f"{float(np.median(errors)):.4f} max {max(errors):.4f}; all: median "
        f"{numbers['logits_check_ratio']:.4f} (tolerance "
        f"{run.traffic['logit_tolerance']}), max "
        f"{numbers['logits_check_ratio_max']:.4f} (tolerance "
        f"{run.traffic['logit_tolerance_single']})")
    return why + more, numbers


def compiled_texts(run, fns, params, batcher):
    """The compiled text of the decode program and of every chunk
    program, so that their operations can be read by scope.  The chunk
    programs (one a context extent) share one name in the trace and
    number their instructions differently: each text goes under
    ``jit__chunk@<extent>`` and ``readers/hyper_latent.py`` reads a run
    against its own."""
    import jax
    import jax.numpy as jnp

    tr = run.traffic
    C, page = int(tr["prefill_chunk"]), int(tr["page_size"])
    max_len = int(tr["pages_per_seq"]) * page
    run.hlo_texts["jit__decode"] = fns.decode_jit.lower(
        params, batcher.pools, batcher.carry,
        jnp.asarray(batcher.cache.page_table)).compile().as_text()
    i32 = jnp.int32(0)
    for start in range(0, int(tr["max_prompt_len"]), C):
        ctx_len = min(-(-(start + C) // page) * page, max_len)
        run.hlo_texts[f"jit__chunk@{ctx_len}"] = fns.chunk_jit.lower(
            params, batcher.pools, jnp.zeros((1, C), jnp.int32), i32, i32,
            i32, jnp.asarray(batcher.cache.page_table[0]),
            jax.random.PRNGKey(0), ctx_len=ctx_len).compile().as_text()


# -------------------------------------------------------------- the run
def run(run) -> dict:
    from apex_tpu.serving.serve import Request

    peaks = {}

    def peak(after: str) -> None:
        stats = run.devices[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks[after] = stats["peak_bytes_in_use"]

    cfg, tr = run.config, run.traffic
    if tr["kind"] != "backlog":
        raise SystemExit("runners/serve_hyper_latent.py: backlog traffic "
                         "only")
    slots, page = int(tr["slots"]), int(tr["page_size"])
    vocab, experts = cfg["vocab_size"], int(cfg["n_routed_experts"])

    model, params, ccfg, fns, make_pools = build(run)
    from apex_tpu.models.xing4 import COUNTER_NAMES
    peak("weights")
    with run.phase("reference_check"):
        # the served side first: its pools are gone (donated, then
        # dropped with the batcher) before the reference takes its room
        served = served_check(run, fns, ccfg, make_pools(), vocab)
        peak("served check")            # every timed program has run
        why, checked = verdict(run, params, slots, *served)
    peak("reference")

    with run.phase("steps_and_pool"):
        batcher = make_batcher(run, fns, ccfg, make_pools())
    with run.phase("warm_window_lengths"):
        # every window length 1..harvest_every stacks another shape
        warm_rng = traffic_gen.rng_for(run.seed, 5)
        for k in range(1, batcher.harvest_every + 1):
            batcher.run([Request(
                uid=("warm", k), max_new_tokens=k + 1,
                prompt=[int(t) for t in traffic_gen.zipf_tokens(
                    warm_rng, vocab, page)])])

    drv = CountingDriver(run, batcher, vocab, Request, names=COUNTER_NAMES)
    source = FileOrderBacklog(tr, vocab, run.seed)
    trace_s = float(tr.get("trace_seconds", 3.0))

    def refill():
        while len(drv.queue) < slots:
            generation = source.next_generation()
            if not drv.budget:
                run.note(f"first generation (pre-aged): "
                         f"{traffic_gen.describe(generation)}")
            for planned in generation:
                drv.submit(planned)

    with run.phase("fill_slots"):
        # until every slot decodes: the first generation's prompts go in
        # one chunk a step
        for _ in range(int(tr["max_fill_pumps"])):
            refill()
            drv.pump()
            if (batcher.live_slots == slots
                    and not batcher.pending_prefill_chunks):
                break
        for _ in range(int(tr["warm_pumps"])):
            refill()
            drv.pump()
    before = run.clock.snapshot()
    drv.bytes_in_use = 0
    chunks_before = batcher.prefill_chunks
    t_open = time.perf_counter()
    t_close = t_open + run.seconds
    run.tracer.arm(t_close - trace_s, t_close)
    while True:
        refill()
        now = drv.pump()
        if now >= t_close:
            break
        run.tracer.poll(now)
    run.tracer.stop()
    compiled = run.clock.snapshot() - before
    peak("window")
    run.note("peak device memory so far, GB, after: " + ", ".join(
        f"{k} {v / 1e9:.2f}" for k, v in peaks.items())
        + f"; most bytes in use at a pump return of the window "
        f"{drv.bytes_in_use / 1e9:.2f}; the device's limit "
        f"{(run.devices[0].memory_stats() or {}).get('bytes_limit', 0) / 1e9:.2f}")
    counters = drv.window_counters(t_open, run.seconds)
    t1 = drv.boundaries[-1][0]
    finished = [u for u, t in drv.t_last.items() if t_open <= t <= t1]
    failed = [u for u in finished if u in drv.invalid]
    counters["tpot_p50_ms"] = timing.percentile(drv.tpot_ms(finished), 50)
    counters["completions"] = len(finished)
    counters["prefill_chunks"] = batcher.prefill_chunks - chunks_before
    # where the nominal close fell in the fixed schedule of pumps
    # (``warm_pumps`` shifts the window along it, PERF.md)
    returns = [t - t_close for t, _ in drv.boundaries if t >= t_open]
    counters["close_after_return_s"] = -returns[-2]
    counters["return_after_close_s"] = returns[-1]
    # the window's whole schedule, from its opening: what ``warm_pumps``
    # is set from (PERF.md section 4)
    counters["pump_returns_s"] = [
        round(t - t_open, 3) for t, _ in drv.boundaries if t >= t_open]
    run.note("pump returns about the nominal close, s: " + ", ".join(
        f"{r:+.3f}" for r in returns[-8:]))
    if "served check" in peaks:
        # the SERVED programs' peak: read when every timed program had
        # run at the window's shapes and before the float32 reference
        # took its room; the window's own boundaries never held more
        counters["served_peak_hbm_gb"] = max(
            peaks["served check"], drv.bytes_in_use) / 1e9
    counted = drv.counted_between(t_open, t1)
    counters.update({"window_" + k: v for k, v in derived_counters(
        counted, experts).items()})
    # the per-layer metrics read the TRACED stretch's counts where there
    # is one (the device times they are set against come from it)
    tracer = run.tracer
    if tracer.t_started is not None:
        counted = drv.counted_between(
            tracer.t_started, tracer.t_stopped or math.inf) or counted
    derived = derived_counters(counted, experts)
    counters.update(derived)
    if derived:
        counters["experts_touched_mean"] = (
            derived["experts_touched_per_step"] / model.n_moe)
    if run.trace:
        compiled_texts(run, fns, params, batcher)
    if failed:
        why.append(f"{len(failed)} request(s) with a wrong token count or "
                   f"a token outside the vocabulary")
    run.note(f"window: {counters['pumps']} pumps over "
             f"{counters['boundary_span_s']:.3f} s between boundaries, "
             f"{counters['tokens_per_s']:.3f} generated tokens/s; "
             f"{len(finished)} completions, slots live mean "
             f"{counters['slots_live_mean']:.2f}; "
             f"{counters.get('window_decode_steps_counted', 0):.0f} decode "
             f"steps and {counters['prefill_chunks']} prefill chunks; the "
             f"nominal close fell {counters['close_after_return_s']:.3f} s "
             f"after a pump's return and "
             f"{counters['return_after_close_s']:.3f} s before the next; "
             f"harness time between a pump's return and the next call: mean "
             f"{counters['host_gap_mean_ms']:.3f} ms, max "
             f"{counters['host_gap_max_ms']:.3f} ms; {len(failed)} failed")
    counters.update(checked, held_experts=experts, layers=ccfg.num_layers,
                    expert_layers=model.n_moe,
                    chunk_tokens=int(tr["prefill_chunk"]))
    return {"t_open": t_open, "correct": not why, "why_incorrect": why,
            "attempted": len(finished), "failed": len(failed),
            "compiled_in_window": dict(compiled),
            "end_to_end": {"serve_tokens_per_s": counters["tokens_per_s"]},
            "counters": counters}
