"""Runner for ``backlog`` traffic on a model that serves from a paged
LATENT cache with sparse selection and held experts (DeepSeek-V3.2, one
chip's share of an expert-parallel deployment).

The served path is the program's own: ``DeepSeekV32Model.decode_fns`` ->
``PagedKVCache`` / ``init_pools`` -> ``ContinuousBatcher.pump`` with
chunked prefill.  The queue, the clock, the books and the rate are
``runners.serve.Driver``'s, the multiset of requests, its pairing and
its pre-ageing ``traffic.Backlog``'s: this file adds the model's build,
its reference check and the decode steps' own counters.

Every seed does the same work.  A 30 s window completes little more
than one generation of 32, so WHICH request follows which decides how
many prompts are ingested inside it, and a random router is skewed
differently for every draw of the weights (a decode step touches 14-21
of the 64 held experts): with both left to ``--seed`` the rate spread
4 % over the driver's runs against the 1 % a cell is admitted at
(PERF.md section 6).  So the FILES fix both, as they already fix the
multiset, its pairing and the pre-ageing: each generation goes in the
block-stratified order ``pairing_seed`` gives (``FileOrderBacklog``)
and the weights are drawn from the configuration's ``weights_seed``.
``--seed`` draws every token id: the window's prompts and the checked
prompt.

``correct`` is decided in set-up, at the published widths, on what the
TIMED programs themselves computed with every slot occupied
(``served_check``): one prompt is served twice, as the first and as the
last request of a batcher whose other slots hold short prompts of their
own, one decode step a ``pump``.  The chunk program hands back the
logits of the prompt's last position, the decode program leaves each
step's logits and selected sets in its carry; both are held to
``reference/deepseek_v32.py``'s full forward on the prompt and the
tokens the server generated after it, with the same weights
(``judge``):

- per layer, the share of a decode step's selected set that the
  reference selected too, median over the steps
  (``selection_overlap_floor``);
- each position's largest logit error as a share of the reference's
  largest |logit|: the median over the positions (``logit_tolerance``:
  a path that computes in fewer bits moves EVERY position) and every
  single position, the two chunk positions among them
  (``logit_tolerance_single``: a fault that hits one step, one page or
  one of the two forms of the attention).
"""

from __future__ import annotations

import collections
import math
import time

import numpy as np

import timing
import traffic as traffic_gen
from reference import deepseek_v32 as reference
from runners.serve import Driver

#: the decode program's own record of a step, in its carry
SHOWN = ("lengths", "last_logits", "last_selected", "last_selected_valid")


class CountingDriver(Driver):
    """``Driver`` that also keeps, at every boundary, the decode steps'
    counter vector as the batcher's harvest brought it and the device's
    bytes in use."""

    def __init__(self, *args, names, **kwargs):
        super().__init__(*args, **kwargs)
        self.names = names
        self.counted = []               # (t, vector)
        self.bytes_in_use = 0           # largest seen at a boundary

    def pump(self) -> float:
        t = super().pump()
        self.counted.append(
            (t, np.asarray(self.batcher.step_counters, np.float64)))
        stats = self.run.devices[0].memory_stats() or {}
        self.bytes_in_use = max(self.bytes_in_use,
                                stats.get("bytes_in_use", 0))
        return t

    def counted_between(self, t0: float, t1: float) -> dict:
        """What the steps counted between the first boundary at or after
        ``t0`` and the last at or before ``t1``; empty without two."""
        inside = [v for t, v in self.counted if t0 <= t <= t1]
        if len(inside) < 2:
            return {}
        return dict(zip(self.names, inside[-1] - inside[0]))


def derived_counters(c: dict, held: int) -> dict:
    """Per-layer metrics' inputs from a span of the decode steps'
    counters."""
    steps = c.get("decode_steps", 0.0)
    if not steps:
        return {}
    out = {
        "decode_steps_counted": steps,
        "moe_held_choice_share": 100.0 * c["decode_choices_held"]
        / max(c["decode_choices"], 1.0),
        "attn_selected_share": 100.0 * c["decode_selected_rows"]
        / max(c["decode_context_rows"], 1.0),
        # what one decode step has to do at the least, from its own counts
        "index_context_tokens_per_step": c["decode_context_rows"] / steps,
        "mla_rows_per_step": c["decode_selected_rows"] / steps,
        "experts_touched_per_step": c["decode_experts_touched"] / steps,
        "held_choices_per_step": c["decode_choices_held"] / steps,
        "live_slot_layers_per_step": c["decode_slot_layers"] / steps,
    }
    if c["decode_choices_held"]:
        out["moe_load_max_over_mean"] = c["decode_load_max"] / (
            c["decode_choices_held"] / held)
    return out


class _OrderFromFile:
    """What ``traffic.Backlog`` asks of its generator: ``permutation``
    (the order of a generation) answered from the file's generator,
    ``random`` (the token ids) from the seed's."""

    def __init__(self, seeded, pairs, pairing_seed: int, block: int = 8):
        self.random = seeded.random
        self.order = np.random.default_rng(pairing_seed + 2)
        # the multiset's indices from the shortest output to the longest
        self.by_output = np.argsort([o for _, o in pairs], kind="stable")
        self.block = block

    def permutation(self, n: int):
        # every ``block`` consecutive requests span the whole range of
        # outputs, as the open loop's schedule does with its lengths
        return self.by_output[traffic_gen.stratified_order(
            self.order, n, self.block)]


class FileOrderBacklog(traffic_gen.Backlog):
    """``traffic.Backlog`` — the same multiset, pairing and pre-ageing —
    with each generation in an order the traffic FILE fixes, the same
    whatever ``--seed`` is; the seed draws the token ids.  In a closed
    backlog whose window holds about one generation the order is the
    work: it decides how many requests finish, and so how many prompts
    are ingested, before the window closes."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        super().__init__(traffic, vocab, seed)
        self.rng = _OrderFromFile(
            self.rng, self.pairs, int(traffic.get("pairing_seed", 0)))


# ------------------------------------------------------------ the build
def build(run):
    """The model on the device from the cell's files (the weights from
    the configuration's ``weights_seed``): (model, params, cache config,
    step functions, pool maker)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    try:
        from apex_tpu.models.deepseek_v32 import (
            DeepSeekV32Config, DeepSeekV32Model,
        )
    except ImportError as e:
        raise SystemExit(
            f"runners/serve_latent_moe.py: this checkout's program has no "
            f"latent-attention expert model ({e})")
    from apex_tpu.serving.kv_cache import KVCacheConfig, init_pools
    from apex_tpu.transformer import parallel_state

    cfg, tr = run.config, run.traffic
    slots, pages_per_seq = int(tr["slots"]), int(tr["pages_per_seq"])
    with run.phase("weights_on_device"):
        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=1)
        mcfg = DeepSeekV32Config.from_hf(
            cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
            held_experts=tuple(cfg["held_experts"]),
            params_dtype=jnp.bfloat16)
        model = DeepSeekV32Model(mcfg)
        on_mesh = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        # one jitted call, in the type they are served in; the
        # generator's own bits ("rbg"): 4.6 B normal draws through
        # threefry take the chip 40 s
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
            latent_dim=mcfg.latent_dim, index_dim=mcfg.index_head_dim)
        fns = model.decode_fns(
            params, mesh, ccfg, max_prompt_len=int(tr["max_prompt_len"]),
            prefill_chunk=int(tr["prefill_chunk"]))
        make_pools = jax.jit(lambda: init_pools(ccfg),
                             out_shardings=on_mesh(fns.pool_specs))
    return model, params, ccfg, fns, make_pools


def make_batcher(run, fns, ccfg, pools, **kwargs):
    from apex_tpu.serving.kv_cache import PagedKVCache
    from apex_tpu.serving.serve import ContinuousBatcher

    return ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), pools,
        max_prompt_len=int(run.traffic["max_prompt_len"]),
        chunk_fn=fns.chunk, prefill_chunk=int(run.traffic["prefill_chunk"]),
        **kwargs)


# ------------------------------------------------------------ the check
def check_plan(traffic: dict):
    """(prompt tokens, tokens the first request generates, decode steps
    the last one takes) of the checked prompt.  The first request has to
    outlive the fill (a chunk a pump: its own, one per other slot, the
    last request's) and the last request's decode steps; the prompt and
    all but the newest of its tokens are the ``check_tokens`` the
    reference walks."""
    slots, chunk = int(traffic["slots"]), int(traffic["prefill_chunk"])
    total, steps = int(traffic["check_tokens"]), int(
        traffic["check_decode_steps"])
    new = (slots - 2) + -(-total // chunk) + steps + 2
    return total - new + 1, new, steps


def served_check(run, fns, ccfg, pools, vocab: int):
    """The checked prompt through the TIMED programs, every slot
    occupied: it is the first request and the last; between them every
    other slot gets a short prompt of its own, and all of them decode
    on, one step a ``pump``, until the last request has taken its steps
    and the first has finished.

    Returns (the prompt; the first request's tokens; per checked request
    its tokens, the chunk program's logits of the prompt's last
    position, the positions its decode steps were read at, their logits
    (steps, vocab), selected positions (steps, layers, K) and which of
    those are real; the fewest live slots during the last request's
    decode steps)."""
    import jax

    from apex_tpu.serving.serve import Request

    tr = run.traffic
    slots, page = ccfg.max_seqs, ccfg.page_size
    n, new, steps = check_plan(tr)
    rng = traffic_gen.rng_for(run.seed, 4)
    tokens_of = lambda length: [int(t) for t in traffic_gen.zipf_tokens(
        rng, vocab, length)]
    prompt = tokens_of(n)
    batcher = make_batcher(run, fns, ccfg, pools, harvest_every=1)
    # the longest context the chunk program has an executable for, so
    # that the peak read after this function has seen every program
    batcher.run([Request(uid=("check", "longest"), max_new_tokens=1,
                         prompt=tokens_of(int(tr["max_prompt_len"]) - 1))])
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
    for _ in range(4 * (new + slots + -(-n // int(tr["prefill_chunk"])))):
        if ("check", "first") in done and ("check", "last") in done:
            break
        live = batcher.live_slots       # decoding or about to, this pump
        batcher.pump(queue)
        shown = jax.device_get({k: batcher.carry[k] for k in SHOWN})
        for slot in np.flatnonzero(batcher.cache.lengths >= n):
            if int(slot) not in read:
                # this pump ran its prompt's last chunk (one a pump)
                read[int(slot)] = {
                    "name": "last" if read else "first", "chunk_at": n - 1,
                    "chunk_logits": np.asarray(
                        batcher.last_prefill_logits, np.float32),
                    "at": [], "logits": [], "selected": [], "valid": []}
        for slot, r in read.items():
            at = int(shown["lengths"][slot]) - 1
            if at > (r["at"] or [n - 1])[-1]:
                if r["name"] == "last":
                    fewest_live = min(fewest_live, live)
                r["at"].append(at)
                r["logits"].append(shown["last_logits"][slot])
                r["selected"].append(shown["last_selected"][:, slot])
                r["valid"].append(shown["last_selected_valid"][:, slot])
    # the batcher's device state goes NOW, not when a collector finds the
    # batcher: the reference needs the room (1.8 GB of pools)
    jax.tree.map(lambda a: a.delete(), (batcher.pools, batcher.carry))
    served = [dict(r, tokens=list(done[("check", r["name"])].tokens),
                   **{k: np.asarray(r[k]) for k in (
                       "at", "logits", "selected", "valid")})
              for r in read.values()]
    return prompt, served[0]["tokens"], served, fewest_live


def reference_prompt(run, params, sequence, n: int):
    """The reference's full forward on ``sequence``: (logits of the
    positions from ``n - 1`` on, per layer the selection masks of the
    positions from ``n`` on)."""
    import jax

    jitted = {}

    def wrap(piece):
        """``piece`` jitted, one call at a time: a call's buffers are
        allocated when it is enqueued, so thirty-odd query blocks queued
        ahead of the device fill its memory whatever a block needs (the
        phase peaked at 16.82 GB at 128 and at 64 queries a block alike;
        my chip runs, PR 28)."""
        if piece not in jitted:
            compiled = jax.jit(piece, static_argnums=(
                reference.STATIC_ARGNUMS.get(piece.__name__, ())))
            jitted[piece] = lambda *args: jax.block_until_ready(
                compiled(*args))
        return jitted[piece]

    L = len(sequence)
    logits, selections = reference.forward(
        params, np.asarray(sequence), reference.from_hf(run.config),
        tuple(run.config["held_experts"]), positions=range(n - 1, L),
        last=L - n, q_block=int(run.traffic["reference_q_block"]), wrap=wrap)
    return (np.asarray(logits, np.float32),
            [np.asarray(s) for s in selections])


def compare(served, n: int, ref_logits, selections):
    """(per layer, every decode step's share of its selected set that
    the reference selected too; every decode position's largest logit
    error as a share of the reference's largest |logit|; the same for
    each chunk position; that |logit|).  ``ref_logits`` start at
    position ``n - 1``, ``selections`` at ``n``."""
    scale = float(np.max(np.abs(ref_logits)))
    error = lambda got, at: float(
        np.max(np.abs(got - ref_logits[at - (n - 1)])) / scale)
    overlaps = [[] for _ in selections]
    errors, chunk_errors = [], []
    for s in served:
        chunk_errors.append(error(s["chunk_logits"], s["chunk_at"]))
        for step, at in enumerate(s["at"]):
            errors.append(error(s["logits"][step], at))
            for layer, selected in enumerate(selections):
                theirs = selected[at - n]
                mine = np.zeros_like(theirs)
                mine[s["selected"][step, layer][s["valid"][step, layer]]] \
                    = True
                overlaps[layer].append(
                    float((mine & theirs).sum() / theirs.sum()))
    return overlaps, errors, chunk_errors, scale


def judge(traffic: dict, overlaps, errors, chunk_errors):
    """(why not correct: a list, empty when correct; the numbers that
    were compared).  Medians AND single readings: one position's error
    has a heavy tail that says nothing of precision (now and then a
    token's 8th and 9th expert swap places between bf16 and float32
    activations and that ONE position moves by 0.06-0.08 of the largest
    logit), so the tight limit sits on the median, which a path that
    really computes in fewer bits moves; the single-position limit
    catches what hits one step, one page or one form only."""
    floor = float(traffic["selection_overlap_floor"])
    tolerance = float(traffic["logit_tolerance"])
    single = float(traffic["logit_tolerance_single"])
    numbers = {
        "selection_overlap_min": min(float(np.median(o)) for o in overlaps),
        "selection_overlap_worst_step": min(min(o) for o in overlaps),
        "logits_check_ratio": float(np.median(errors + chunk_errors)),
        "logits_check_ratio_max": max(errors + chunk_errors),
        "logits_check_ratio_chunk_max": max(chunk_errors),
    }
    why = []
    if not numbers["selection_overlap_min"] >= floor:
        why.append(f"the decode steps' selected sets share only "
                   f"{numbers['selection_overlap_min']} with the "
                   f"reference's (median over the steps; floor {floor})")
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
    overlaps, errors, chunk_errors, scale = compare(
        served, n, *reference_prompt(run, params, prompt + tokens[:-1], n))
    more, numbers = judge(run.traffic, overlaps, errors, chunk_errors)
    run.note(
        f"reference: a prompt of {n} tokens served as the first and the "
        f"last of {slots} requests (chunked prefill, then "
        f"{[len(s['at']) for s in served]} paged decode steps with "
        f"{fewest_live} slots live at the least) vs the float32 reference "
        f"on {n + len(tokens) - 1} tokens; selected-set overlap per layer, "
        f"median over the steps "
        f"{[round(float(np.median(o)), 4) for o in overlaps]} (floor "
        f"{run.traffic['selection_overlap_floor']}), worst step "
        f"{numbers['selection_overlap_worst_step']:.4f}; logits, share of "
        f"max |logit| {scale:.3f}: chunk positions "
        f"{[round(e, 4) for e in chunk_errors]}, decode positions median "
        f"{float(np.median(errors)):.4f} max {max(errors):.4f}; all: median "
        f"{numbers['logits_check_ratio']:.4f} (tolerance "
        f"{run.traffic['logit_tolerance']}), max "
        f"{numbers['logits_check_ratio_max']:.4f} (tolerance "
        f"{run.traffic['logit_tolerance_single']})")
    return why + more, numbers


# -------------------------------------------------------------- the run
def run(run) -> dict:
    import jax.numpy as jnp

    from apex_tpu.serving.serve import Request

    peaks = {}

    def peak(after: str) -> None:
        stats = run.devices[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks[after] = stats["peak_bytes_in_use"]

    cfg, tr = run.config, run.traffic
    if tr["kind"] != "backlog":
        raise SystemExit("runners/serve_latent_moe.py: backlog traffic only")
    slots, page = int(tr["slots"]), int(tr["page_size"])
    vocab = cfg["vocab_size"]
    held = tuple(cfg["held_experts"])

    model, params, ccfg, fns, make_pools = build(run)
    from apex_tpu.models.deepseek_v32 import COUNTER_NAMES
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
    # every run walks the same schedule, so the nominal close falls at
    # the same place in it: a close that sits ON a pump's return lets
    # the clock's wobble (some 30 ms) decide whether one more pump is
    # counted, and a pump without a chunk moves the rate by 1 %.
    # ``warm_pumps`` shifts the window along the schedule (PERF.md)
    returns = [t - t_close for t, _ in drv.boundaries if t >= t_open]
    counters["close_after_return_s"] = -returns[-2]
    counters["return_after_close_s"] = returns[-1]
    if "served check" in peaks:
        # the SERVED program's peak: read when every timed program had
        # run at the window's shapes and before the float32 reference
        # took its room (which is what the whole run's peak shows); the
        # window's own boundaries never held more
        counters["served_peak_hbm_gb"] = max(
            peaks["served check"], drv.bytes_in_use) / 1e9
    counted = drv.counted_between(t_open, t1)
    counters.update({"window_" + k: v for k, v in derived_counters(
        counted, len(held)).items()})
    # the per-layer metrics read the TRACED stretch's counts where there
    # is one (the device times they are set against come from it)
    tracer = run.tracer
    if tracer.t_started is not None:
        counted = drv.counted_between(
            tracer.t_started, tracer.t_stopped or math.inf) or counted
    counters.update(derived_counters(counted, len(held)))
    if run.trace:
        # the compiled text of the decode program, so that its
        # operations can be read by scope (an executable's text is the
        # same whether it was compiled or read from the cache)
        run.hlo_texts["jit__decode"] = fns.decode_jit.lower(
            params, batcher.pools, batcher.carry,
            jnp.asarray(batcher.cache.page_table)).compile().as_text()
    if failed:
        why.append(f"{len(failed)} request(s) with a wrong token count or "
                   f"a token outside the vocabulary slice")
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
    counters.update(checked, held_experts=len(held),
                    layers=ccfg.num_layers, expert_layers=model.n_moe)
    return {"t_open": t_open, "correct": not why, "why_incorrect": why,
            "attempted": len(finished), "failed": len(failed),
            "compiled_in_window": dict(compiled),
            "end_to_end": {"serve_tokens_per_s": counters["tokens_per_s"]},
            "counters": counters}
