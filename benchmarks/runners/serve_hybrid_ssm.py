"""Runner for ``backlog`` traffic on a model whose every layer is a
PARALLEL hybrid: a Mamba-2 state-space mixer beside grouped-query
attention (Falcon-H1).  A slot keeps two kinds of state: K/V in pages,
and a fixed-size recurrent state and convolution window per layer.

The served path is the program's own: ``FalconH1Model.decode_fns`` ->
``model.cache_config`` / ``PagedKVCache`` / ``init_pools`` (the K/V pool
and the two state pools in one donated dict) ->
``ContinuousBatcher.pump`` with chunked prefill, the chunk step told its
slot.  The queue, the clock, the books and the rate are
``runners.serve.Driver``'s, the decode steps' counters
``runners.serve_latent_moe.CountingDriver``'s, the order of a
generation ``FileOrderBacklog``'s (the files fix the multiset, its
pairing, the order and the pre-ageing; ``weights_seed`` the weights;
``--seed`` the token ids): this file adds the model's build, the
reference check and the readings of the state.

``correct`` is decided in set-up, at the published widths, on what the
TIMED programs themselves computed with every slot occupied
(``served_check``): one prompt of three chunks, the last of them
padded, is served as the first and as the last request of a batcher
whose other slots hold short prompts of their own, one decode step a
``pump``; the last request's chunks run while every other slot decodes
between them (its state is carried from chunk to chunk across those
steps).  Every chunk program's logits (the chunk's last real position),
each decode step's logits (from the carry) and the final recurrent
state of both slots (read from the pool) are held to
``reference/falcon_h1.py``'s full forward on the prompt and the tokens
the server generated after it, with the same weights (``judge``): each
position's largest logit error as a share of the reference's largest
|logit|, the median over the positions and every single position, and
each slot's state error as a share of the reference state's norm.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time

import numpy as np

import timing
import traffic as traffic_gen
from reference import falcon_h1 as reference
from runners.serve_latent_moe import (
    CountingDriver, FileOrderBacklog, check_plan, make_batcher,
)

def derived_counters(c: dict) -> dict:
    """Per-layer metrics' inputs from a span of the decode steps'
    counters."""
    steps = c.get("decode_steps", 0.0)
    if not steps:
        return {}
    return {
        "decode_steps_counted": steps,
        # what one decode step has to do at the least, from its own counts
        "full_rows_per_step": c["decode_full_rows"] / steps,
        "context_tokens_per_step": c["decode_context_rows"] / steps,
        "live_slot_layers_per_step": c["decode_slot_layers"] / steps,
        "ssm_state_bytes_per_step": c["ssm_state_bytes"] / steps,
    }


# ------------------------------------------------------------ the build
def build(run):
    """The model on the device from the cell's files (the weights from
    the configuration's ``weights_seed``): (model, params, cache config,
    step functions, pool maker)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    try:
        from apex_tpu.models.falcon_h1 import FalconH1Config, FalconH1Model
    except ImportError as e:
        raise SystemExit(
            f"runners/serve_hybrid_ssm.py: this checkout's program has no "
            f"hybrid state-space model ({e})")
    from apex_tpu.serving.kv_cache import init_pools
    from apex_tpu.transformer import parallel_state

    cfg, tr = run.config, run.traffic
    slots = int(tr["slots"])
    with run.phase("weights_on_device"):
        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=1)
        model = FalconH1Model(FalconH1Config.from_hf(
            cfg, params_dtype=jnp.bfloat16))
        on_mesh = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        # one jitted call, in the type they are served in, from the
        # generator's own bits ("rbg": threefry over billions of normal
        # draws takes the chip most of a minute)
        params = jax.jit(model.init, out_shardings=on_mesh(
            model.param_specs()))(jax.random.key(
                int(cfg["weights_seed"]), impl="rbg"))
        jax.block_until_ready(params)
    with run.phase("steps_and_pool"):
        ccfg = model.cache_config(
            slots=slots, pages_per_seq=int(tr["pages_per_seq"]),
            page_size=int(tr["page_size"]), dtype=jnp.bfloat16)
        fns = model.decode_fns(
            params, mesh, ccfg, max_prompt_len=int(tr["max_prompt_len"]),
            prefill_chunk=int(tr["prefill_chunk"]))
        make_pools = jax.jit(lambda: init_pools(ccfg),
                             out_shardings=on_mesh(fns.pool_specs))
    return model, params, ccfg, fns, make_pools


# ------------------------------------------------------------ the check
def served_check(run, fns, ccfg, pools, vocab: int):
    """The checked prompt through the TIMED programs, every slot
    occupied: it is the first request and the last; between them every
    other slot gets a short prompt of its own, and all of them decode
    on, one step a ``pump``, until the last request has taken its steps
    and the first has finished.

    Returns (the prompt; the first request's tokens; per checked request
    its tokens, every chunk's (start, logits, decode steps run before
    it), the positions its decode steps were read at with their logits
    (steps, vocab), its context length at the end and its final
    recurrent state (layers, H, P, N) fp32; the fewest live slots during
    the last request's decode steps).  The checked prompt's chunks run
    the chunk program at every context bucket a prompt of the window
    needs, so that the peak read after this function has seen every
    program the window runs (the longer buckets run only in the fill,
    for the pre-aged first generation)."""
    import jax

    from apex_tpu.models.falcon_h1 import STATE
    from apex_tpu.serving.serve import Request

    tr = run.traffic
    slots, page = ccfg.max_seqs, ccfg.page_size
    n, new, steps = check_plan(tr)
    n_chunks = -(-n // int(tr["prefill_chunk"]))
    rng = traffic_gen.rng_for(run.seed, 4)
    tokens_of = lambda length: [int(t) for t in traffic_gen.zipf_tokens(
        rng, vocab, length)]
    prompt = tokens_of(n)
    chunks = collections.defaultdict(list)      # slot -> the checked chunks
    box = {}

    def chunk(pools, toks, start, plen, write_from, row, key, *, slot):
        pools, tok, logits = fns.chunk(pools, toks, start, plen, write_from,
                                       row, key, slot=slot)
        if int(plen) == n:
            chunks[int(slot)].append((int(start), logits, box["b"].steps))
        return pools, tok, logits

    chunk.prefill_chunk = fns.chunk.prefill_chunk
    batcher = box["b"] = make_batcher(
        run, dataclasses.replace(fns, chunk=chunk), ccfg, pools,
        harvest_every=1)
    queue = collections.deque(
        [Request(uid=("check", "first"), prompt=prompt, max_new_tokens=new)]
        + [Request(uid=("check", "other", i), max_new_tokens=new,
                   prompt=tokens_of(int(rng.integers(page, 2 * page + 1))))
           for i in range(slots - 2)]
        + [Request(uid=("check", "last"), prompt=prompt,
                   max_new_tokens=steps + 1)])
    read = {}                   # slot -> what its request showed
    fewest_live = slots
    done = batcher.completions
    for _ in range(4 * (new + slots + n_chunks)):
        if ("check", "first") in done and ("check", "last") in done:
            break
        live = batcher.live_slots       # decoding or about to, this pump
        batcher.pump(queue)
        for slot, ran in chunks.items():
            if slot not in read and len(ran) == n_chunks:
                # this pump ran the checked prompt's last chunk (one a
                # pump); by its LENGTH a slot is not told apart: the
                # other slots' decode steps take them past ``n``
                read[slot] = {"name": "last" if read else "first",
                              "at": [], "logits": []}
        if not read:
            continue
        # the checked slots' rows only: a step's logits are 100 MB
        lengths, rows = jax.device_get((batcher.carry["lengths"],
                                        batcher.carry["last_logits"][
                                            np.asarray(list(read))]))
        for (slot, r), row in zip(read.items(), rows):
            at = int(lengths[slot]) - 1
            if at > (r["at"] or [n - 1])[-1]:
                if r["name"] == "last":
                    fewest_live = min(fewest_live, live)
                r["at"].append(at)
                r["logits"].append(row)
    lengths = jax.device_get(batcher.carry["lengths"])
    served = []
    for slot, r in read.items():
        served.append(dict(
            r, tokens=list(done[("check", r["name"])].tokens),
            at=np.asarray(r["at"]), logits=np.asarray(r["logits"]),
            chunks=[(s, np.asarray(lg, np.float32), k)
                    for s, lg, k in chunks[slot]],
            length=int(lengths[slot]),
            state=np.asarray(jax.device_get(batcher.pools[STATE][:, slot]),
                             np.float32)))
    # the batcher's device state goes NOW, not when a collector finds the
    # batcher (it and the recording chunk refer to each other): the
    # reference needs the room
    jax.tree.map(lambda a: a.delete(), (batcher.pools, batcher.carry))
    box.clear()
    return prompt, served[0]["tokens"], served, fewest_live


def reference_prompt(run, params, sequence, positions, state_at: int):
    """The reference's full forward on ``sequence``: (logits at
    ``positions``, per layer the final state, per layer the state after
    ``state_at`` tokens).  One jitted piece at a time: a call's buffers
    are allocated when it is enqueued."""
    import jax

    jitted = {}

    def wrap(piece):
        if piece not in jitted:
            compiled = jax.jit(piece, static_argnums=(
                reference.STATIC_ARGNUMS.get(piece.__name__, ())))
            jitted[piece] = lambda *args: jax.block_until_ready(
                compiled(*args))
        return jitted[piece]

    logits, finals, kept = reference.forward(
        params, np.asarray(sequence), reference.from_hf(run.config),
        positions=positions, state_at=state_at,
        head_block=int(run.traffic["reference_head_block"]), wrap=wrap)
    return (np.asarray(logits, np.float32), np.asarray(finals, np.float32),
            np.asarray(kept, np.float32))


def compare(served, n: int, C: int, ref_logits, positions, ref_finals,
            ref_kept):
    """(every decode position's largest logit error as a share of the
    reference's largest |logit|; the same for each chunk's last real
    position; each checked slot's final state error, a norm's share of
    the reference state's norm)."""
    row = {p: i for i, p in enumerate(positions)}
    scale = float(np.max(np.abs(ref_logits)))
    error = lambda got, at: float(
        np.max(np.abs(got - ref_logits[row[at]])) / scale)
    errors, chunk_errors, state_errors = [], [], []
    for s in served:
        for start, logits, _ in s["chunks"]:
            chunk_errors.append(error(logits, min(n, start + C) - 1))
        errors += [error(lg, at) for at, lg in zip(s["at"], s["logits"])]
        want = ref_finals if s["name"] == "first" else ref_kept
        state_errors.append(float(np.linalg.norm(s["state"] - want)
                                  / np.linalg.norm(want)))
    return errors, chunk_errors, state_errors, scale


def judge(traffic: dict, errors, chunk_errors, state_errors):
    """(why not correct: a list, empty when correct; the numbers that
    were compared).  The tight limit sits on the median over positions
    (a path that computes in fewer bits moves EVERY position); the
    single-position limit catches what hits one chunk or one step; the
    state limit reads the recurrent state itself, where a fault in how
    it is carried or stored shows before it reaches a logit."""
    numbers = {
        "logits_check_ratio": float(np.median(errors + chunk_errors)),
        "logits_check_ratio_max": max(errors + chunk_errors),
        "logits_check_ratio_chunk_max": max(chunk_errors),
        "state_check_ratio": max(state_errors),
    }
    limits = (
        ("logits_check_ratio", "logit_tolerance",
         "served logits, median over positions"),
        ("logits_check_ratio_max", "logit_tolerance_single",
         "served logits at one position"),
        ("state_check_ratio", "state_tolerance",
         "a checked slot's final SSM state (norm of the difference)"),
    )
    why = [f"{what} differ(s) from the reference by {numbers[name]} of its "
           f"size (tolerance {traffic[limit]})"
           for name, limit, what in limits
           if not numbers[name] <= float(traffic[limit])]
    return why, numbers


def verdict(run, params, slots: int, prompt, tokens, served, fewest_live):
    """What ``served_check`` returned against the reference's forward
    with ``params``: (why not correct, the numbers compared)."""
    tr = run.traffic
    n, C = len(prompt), int(tr["prefill_chunk"])
    n_chunks = -(-n // C)
    steps = int(tr["check_decode_steps"])
    why = []
    for s in served:
        if s["tokens"] != tokens[:len(s["tokens"])]:
            why.append(f"one prompt served in two slots gave different "
                       f"tokens: {s['name']} {s['tokens']} against {tokens}")
    if fewest_live < slots:
        why.append(f"only {fewest_live} of {slots} slots were live during "
                   f"the checked decode steps")
    if len(served) != 2 or any(len(s["at"]) < steps or len(s["chunks"])
                               != n_chunks for s in served):
        why.append(f"the checked prompt was read in {len(served)} slots, "
                   f"{[len(s['chunks']) for s in served]} chunks and "
                   f"{[len(s['at']) for s in served]} decode steps")
    last = [s for s in served if s["name"] == "last"]
    between = [b - a for (_, _, a), (_, _, b) in zip(
        last[0]["chunks"], last[0]["chunks"][1:])] if last else []
    if not between or min(between) < 1:
        why.append(f"the last request's chunks were not interleaved with "
                   f"decode steps of the other slots: {between}")
    L = n + len(tokens) - 1
    positions = sorted({min(n, c * C + C) - 1 for c in range(n_chunks)}
                       | set(range(n - 1, L)))
    state_at = last[0]["length"] if last else n
    ref_logits, ref_finals, ref_kept = reference_prompt(
        run, params, prompt + tokens[:-1], positions, state_at)
    errors, chunk_errors, state_errors, scale = compare(
        served, n, C, ref_logits, positions, ref_finals, ref_kept)
    more, numbers = judge(tr, errors, chunk_errors, state_errors)
    run.note(
        f"reference: a prompt of {n} tokens ({n_chunks} chunks) served as "
        f"the first and the last of {slots} requests (the last one's chunks "
        f"{between} decode steps apart), then "
        f"{[len(s['at']) for s in served]} paged decode steps with "
        f"{fewest_live} slots live at the least, vs the float32 reference on "
        f"{L} tokens; logits, share of max |logit| {scale:.3f}: chunk "
        f"positions {[round(e, 4) for e in chunk_errors]}, decode positions "
        f"median {float(np.median(errors)):.4f} max {max(errors):.4f}; all: "
        f"median {numbers['logits_check_ratio']:.4f} (tolerance "
        f"{tr['logit_tolerance']}), max "
        f"{numbers['logits_check_ratio_max']:.4f} (tolerance "
        f"{tr['logit_tolerance_single']}); final SSM state of the two "
        f"slots (after {[s['length'] for s in served]} tokens), "
        f"share of its norm {[round(e, 5) for e in state_errors]} "
        f"(tolerance {tr['state_tolerance']})")
    return why + more, numbers


def compiled_texts(run, fns, params, batcher) -> None:
    """The compiled text of the decode program and of the chunk program
    at each context extent (``<module>@<extent>``), so that their
    operations can be read by scope."""
    import jax
    import jax.numpy as jnp

    run.hlo_texts["jit__decode"] = fns.decode_jit.lower(
        params, batcher.pools, batcher.carry,
        jnp.asarray(batcher.cache.page_table)).compile().as_text()
    C, i32 = int(run.traffic["prefill_chunk"]), jnp.int32(0)
    for ctx_len in fns.chunk.ctx_buckets:
        run.hlo_texts[f"jit__chunk@{ctx_len}"] = fns.chunk_jit.lower(
            params, batcher.pools, jnp.zeros((1, C), jnp.int32), i32, i32,
            i32, jnp.asarray(batcher.cache.page_table[0]),
            jax.random.PRNGKey(0), i32, ctx_len=ctx_len).compile().as_text()


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
        raise SystemExit("runners/serve_hybrid_ssm.py: backlog traffic only")
    slots, page = int(tr["slots"]), int(tr["page_size"])
    vocab = cfg["vocab_size"]

    model, params, ccfg, fns, make_pools = build(run)
    from apex_tpu.models.falcon_h1 import COUNTER_NAMES
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
        # one chunk a decode step
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
        f"{(run.devices[0].memory_stats() or {}).get('bytes_limit', 0)/1e9:.2f}")
    counters = drv.window_counters(t_open, run.seconds)
    t1 = drv.boundaries[-1][0]
    finished = [u for u, t in drv.t_last.items() if t_open <= t <= t1]
    failed = [u for u in finished if u in drv.invalid]
    counters["tpot_p50_ms"] = timing.percentile(drv.tpot_ms(finished), 50)
    counters["completions"] = len(finished)
    counters["prefill_chunks"] = batcher.prefill_chunks - chunks_before
    # where the nominal close fell in the fixed schedule of pumps
    # (``warm_pumps`` shifts the window along it, PERF.md section 4)
    returns = [t - t_close for t, _ in drv.boundaries if t >= t_open]
    counters["close_after_return_s"] = -returns[-2]
    counters["return_after_close_s"] = returns[-1]
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
    counters.update({"window_" + k: v
                     for k, v in derived_counters(counted).items()})
    # the per-layer metrics read the TRACED stretch's counts where there
    # is one (the device times they are set against come from it)
    tracer = run.tracer
    if tracer.t_started is not None:
        counted = drv.counted_between(
            tracer.t_started, tracer.t_stopped or math.inf) or counted
    counters.update(derived_counters(counted))
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
    counters.update(checked, layers=ccfg.num_layers,
                    chunk_tokens=int(tr["prefill_chunk"]))
    return {"t_open": t_open, "correct": not why, "why_incorrect": why,
            "attempted": len(finished), "failed": len(failed),
            "compiled_in_window": dict(compiled),
            "end_to_end": {"serve_tokens_per_s": counters["tokens_per_s"]},
            "counters": counters}
