"""Runner for ``backlog`` traffic on a model that serves from TWO page
classes (full layers keep the whole context, window layers a ring) with
grouped-query heads and held experts (Arcee ``afmoe`` / Trinity, one
chip's share of an expert-parallel deployment).

The served path is the program's own: ``AfmoeModel.decode_fns`` ->
``KVCacheConfig.of_classes`` / ``PagedKVCache`` / ``init_pools`` ->
``ContinuousBatcher.pump`` with chunked prefill.  The queue, the clock,
the books and the rate are ``runners.serve.Driver``'s, the decode steps'
counters ``runners.serve_latent_moe.CountingDriver``'s, the order of a
generation ``FileOrderBacklog``'s: this file adds the model's build, the
two-part prompt multiset, the per-class page gauges and the reference
check.

**Traffic.**  ``traffic.quantile_values`` knows one distribution a
file; here a generation's prompts are TWO log-uniform parts (the
``prompt.parts`` of the traffic file, each at its own fixed quantiles),
so ``MixedBacklog`` builds the multiset itself: the outputs' quantiles
are cut into 8 strata from the shortest to the longest, the strata go to
the parts in turn, and inside a part the pairing of prompt and output is
the permutation ``pairing_seed`` gives.  ``FileOrderBacklog``'s order
(every 8 consecutive requests take one output from each stratum) then
holds 4 short and 4 long prompts in every 8 and spans the outputs.  As
in the latent cell the FILES fix the multiset, its pairing, the order of
every generation and the pre-ageing, the configuration fixes the weights
(``weights_seed``), and ``--seed`` draws the token ids.

``correct`` is decided in set-up, at the published widths, on what the
TIMED programs themselves computed with every slot occupied
(``served_check``): one long prompt (two windows deep, so that the
window layers' rings have turned over) is served twice, as the first
and as the last request of a batcher whose other slots hold short
prompts, one decode step a ``pump``.  The chunk program hands back the
logits of the prompt's last position; the decode program leaves each
step's logits and the attention output of its last window layer and its
last full layer in the carry.  All are held to ``reference/afmoe.py``'s
full forward on the prompt and the tokens the server generated after it,
with the same weights (``judge``): each position's largest error as a
share of the reference's largest magnitude, the median over the
positions and every single position, for the logits and for the two
attention outputs.
"""

from __future__ import annotations

import collections
import math
import time

import numpy as np

import timing
import traffic as traffic_gen
from reference import afmoe as reference
from runners.serve_latent_moe import (
    CountingDriver, FileOrderBacklog, _OrderFromFile, check_plan,
    make_batcher,
)

#: the decode program's own record of a step, in its carry
SHOWN = ("lengths", "last_logits", "last_attn")


# ---------------------------------------------------------- the traffic
def mixed_pairs(traffic: dict, n: int, strata: int = 8):
    """The ``n`` (prompt, output) pairs the file fixes: ``prompt.parts``
    each at its share's quantiles, the outputs at ``n``; output strata
    (``strata`` runs of consecutive ranks) go to the parts in turn, and
    inside a part prompts meet outputs by ``pairing_seed``'s
    permutation."""
    parts = traffic["prompt"]["parts"]
    outputs = sorted(traffic_gen.quantile_values(traffic["output"], n))
    per = -(-n // strata)
    ranks = [[r for r in range(n) if (r // per) % len(parts) == i]
             for i in range(len(parts))]
    order = np.random.default_rng(int(traffic.get("pairing_seed", 0)))
    pairs = [None] * n
    for part, mine in zip(parts, ranks):
        if len(mine) != round(float(part["share"]) * n):
            raise ValueError(
                f"a part's share {part['share']} of {n} requests does not "
                f"fill its {len(mine)} output ranks")
        prompts = traffic_gen.quantile_values(part, len(mine))
        for rank, j in zip(mine, order.permutation(len(mine))):
            pairs[rank] = (prompts[int(j)], outputs[rank])
    limit = int(traffic["max_total_len"])
    for p, o in pairs:
        if p + o > limit:
            raise ValueError(f"prompt {p} + output {o} exceeds {limit}")
    return pairs


class MixedBacklog(FileOrderBacklog):
    """``FileOrderBacklog`` over the two-part multiset."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        parts = traffic["prompt"]["parts"]
        envelope = {"dist": "loguniform",
                    "lo": min(int(p["lo"]) for p in parts),
                    "hi": max(int(p["hi"]) for p in parts)}
        traffic_gen.Backlog.__init__(
            self, dict(traffic, prompt=envelope), vocab, seed)
        self.traffic = traffic
        self.pairs = mixed_pairs(traffic, self.n)
        self.rng = _OrderFromFile(
            self.rng, self.pairs, int(traffic.get("pairing_seed", 0)))


# ------------------------------------------------------------ the books
class ClassCountingDriver(CountingDriver):
    """``CountingDriver`` that also keeps, at every boundary, each page
    class's pages that hold tokens and its pages allocated."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.class_gauges = []          # (t, {class: (holding, allocated)})

    def pump(self) -> float:
        t = super().pump()
        cache = self.batcher.cache
        cfg = cache.config
        in_use = cache.pages_in_use()
        self.class_gauges.append((t, {
            c.name: (sum(c.pages_for(int(n), cfg.page_size)
                         for n in cache.lengths), in_use[c.name],
                     c.num_pages - 1)
            for c in cfg.page_classes}))
        return t

    def class_counters(self, t0: float, t1: float) -> dict:
        inside = [g for t, g in self.class_gauges if t0 <= t <= t1]
        out = {}
        for name in (inside[0] if inside else {}):
            held = [g[name][0] / g[name][1] for g in inside if g[name][1]]
            out[f"pages_in_use_share_{name}"] = (
                100.0 * sum(held) / len(held) if held else None)
            out[f"pool_allocated_share_{name}"] = 100.0 * sum(
                g[name][1] / g[name][2] for g in inside) / len(inside)
        return out


def derived_counters(c: dict, held: int, window_layers: int) -> dict:
    """Per-layer metrics' inputs from a span of the decode steps'
    counters."""
    steps = c.get("decode_steps", 0.0)
    if not steps:
        return {}
    out = {
        "decode_steps_counted": steps,
        "moe_held_choice_share": 100.0 * c["decode_choices_held"]
        / max(c["decode_choices"], 1.0),
        # rows the window layers' walks read over the rows a walk of the
        # whole context would have read: 100 where no window engaged
        "attn_window_read_share": 100.0 * c["decode_window_rows"]
        / max(c["decode_context_rows"] * window_layers, 1.0),
        # what one decode step has to do at the least, from its own counts
        "window_rows_per_step": c["decode_window_rows"] / steps,
        "full_rows_per_step": c["decode_full_rows"] / steps,
        "context_tokens_per_step": c["decode_context_rows"] / steps,
        "experts_touched_per_step": c["decode_experts_touched"] / steps,
        "held_choices_per_step": c["decode_choices_held"] / steps,
        "live_slot_layers_per_step": c["decode_slot_layers"] / steps,
    }
    if c["decode_choices_held"]:
        out["moe_load_max_over_mean"] = c["decode_load_max"] / (
            c["decode_choices_held"] / held)
    return out


# ------------------------------------------------------------ the build
def build(run):
    """The model on the device from the cell's files (the weights from
    the configuration's ``weights_seed``): (model, params, cache config,
    step functions, pool maker)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    try:
        from apex_tpu.models.afmoe import AfmoeConfig, AfmoeModel
    except ImportError as e:
        raise SystemExit(
            f"runners/serve_window_moe.py: this checkout's program has no "
            f"window-and-full-attention expert model ({e})")
    from apex_tpu.serving.kv_cache import KVCacheConfig, init_pools
    from apex_tpu.transformer import parallel_state

    cfg, tr = run.config, run.traffic
    slots, page = int(tr["slots"]), int(tr["page_size"])
    with run.phase("weights_on_device"):
        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=1)
        mcfg = AfmoeConfig.from_hf(
            cfg, num_experts=cfg["published"]["num_experts"],
            held_experts=tuple(cfg["held_experts"]),
            params_dtype=jnp.bfloat16)
        model = AfmoeModel(mcfg)
        on_mesh = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        # one jitted call, in the type they are served in, from the
        # generator's own bits ("rbg": billions of normal draws through
        # threefry take the chip most of a minute)
        params = jax.jit(model.init, out_shardings=on_mesh(
            model.param_specs()))(jax.random.key(
                int(cfg["weights_seed"]), impl="rbg"))
        jax.block_until_ready(params)
    with run.phase("steps_and_pool"):
        ccfg = KVCacheConfig.of_classes(
            model.cache_classes(
                slots=slots, pages_per_seq=int(tr["pages_per_seq"]),
                page_size=page, prefill_chunk=int(tr["prefill_chunk"])),
            page_size=page, max_seqs=slots, dtype=jnp.bfloat16)
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
    its tokens, the chunk program's logits of the prompt's last
    position, the positions its decode steps were read at, their logits
    (steps, vocab) and attention outputs (steps, 2, heads * d); the
    fewest live slots during the last request's decode steps)."""
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
    # every context bucket the chunk program has an executable for, so
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
                    "at": [], "logits": [], "attn": []}
        for slot, r in read.items():
            at = int(shown["lengths"][slot]) - 1
            if at > (r["at"] or [n - 1])[-1]:
                if r["name"] == "last":
                    fewest_live = min(fewest_live, live)
                r["at"].append(at)
                r["logits"].append(shown["last_logits"][slot])
                r["attn"].append(shown["last_attn"][:, slot])
    # the batcher's device state goes NOW, not when a collector finds the
    # batcher: the reference needs the room (3.3 GB of pools)
    jax.tree.map(lambda a: a.delete(), (batcher.pools, batcher.carry))
    served = [dict(r, tokens=list(done[("check", r["name"])].tokens),
                   **{k: np.asarray(r[k]) for k in ("at", "logits", "attn")})
              for r in read.values()]
    return prompt, served[0]["tokens"], served, fewest_live


def reference_prompt(run, params, sequence, n: int):
    """The reference's full forward on ``sequence``: (logits of the
    positions from ``n - 1`` on, per layer the attention outputs there).
    One jitted piece at a time (``serve_latent_moe.reference_prompt``
    says why)."""
    import jax

    jitted = {}

    def wrap(piece):
        if piece not in jitted:
            compiled = jax.jit(piece, static_argnums=(
                reference.STATIC_ARGNUMS.get(piece.__name__, ())))
            jitted[piece] = lambda *args: jax.block_until_ready(
                compiled(*args))
        return jitted[piece]

    logits, attn = reference.forward(
        params, np.asarray(sequence), reference.from_hf(run.config),
        tuple(run.config["held_experts"]),
        positions=range(n - 1, len(sequence)),
        q_block=int(run.traffic["reference_q_block"]), wrap=wrap)
    return np.asarray(logits, np.float32), np.asarray(attn, np.float32)


def compare(served, n: int, ref_logits, ref_attn, layers):
    """(every decode position's largest logit error as a share of the
    reference's largest |logit|; the same for each chunk position; per
    kind of layer every decode position's largest attention-output error
    as a share of that layer's largest magnitude).  ``ref_*`` start at
    position ``n - 1``; ``layers`` are the (window, full) layers the
    decode program shows."""
    scale = float(np.max(np.abs(ref_logits)))
    error = lambda got, at: float(
        np.max(np.abs(got - ref_logits[at - (n - 1)])) / scale)
    errors, chunk_errors = [], []
    attn_errors = [[] for _ in layers]
    for s in served:
        chunk_errors.append(error(s["chunk_logits"], s["chunk_at"]))
        for step, at in enumerate(s["at"]):
            errors.append(error(s["logits"][step], at))
            for kind, layer in enumerate(layers):
                want = ref_attn[layer]
                attn_errors[kind].append(float(
                    np.max(np.abs(s["attn"][step, kind] - want[at - (n - 1)]))
                    / np.max(np.abs(want))))
    return errors, chunk_errors, attn_errors, scale


def judge(traffic: dict, errors, chunk_errors, attn_errors):
    """(why not correct: a list, empty when correct; the numbers that
    were compared).  Medians AND single readings, as in the latent cell:
    a path that computes in fewer bits moves EVERY position, so the
    tight limits sit on the medians; the single-position limits catch
    what hits one step, one page or one kind of layer.  The attention
    outputs are compared beside the logits because a logit is four norms
    and a router away from a layer's attention: a window layer that sees
    too much, or a full layer that sees too little, moves its own output
    far more than it moves the largest logit."""
    numbers = {
        "logits_check_ratio": float(np.median(errors + chunk_errors)),
        "logits_check_ratio_max": max(errors + chunk_errors),
        "logits_check_ratio_chunk_max": max(chunk_errors),
        "attn_window_check_ratio": float(np.median(attn_errors[0])),
        "attn_window_check_ratio_max": max(attn_errors[0]),
        "attn_full_check_ratio": float(np.median(attn_errors[1])),
        "attn_full_check_ratio_max": max(attn_errors[1]),
    }
    limits = (
        ("logits_check_ratio", "logit_tolerance",
         "served logits, median over positions"),
        ("logits_check_ratio_max", "logit_tolerance_single",
         "served logits at one position"),
        ("attn_window_check_ratio", "attn_tolerance",
         "the last window layer's attention output, median over steps"),
        ("attn_window_check_ratio_max", "attn_tolerance_single",
         "the last window layer's attention output at one step"),
        ("attn_full_check_ratio", "attn_tolerance",
         "the full layer's attention output, median over steps"),
        ("attn_full_check_ratio_max", "attn_tolerance_single",
         "the full layer's attention output at one step"),
    )
    why = [f"{what} differ(s) from the reference by {numbers[name]} of the "
           f"largest magnitude (tolerance {traffic[limit]})"
           for name, limit, what in limits
           if not numbers[name] <= float(traffic[limit])]
    return why, numbers


def verdict(run, model, params, slots: int, prompt, tokens, served,
            fewest_live):
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
    if len(served) != 2 or any(
            len(s["at"]) < int(run.traffic["check_decode_steps"])
            for s in served):
        why.append(f"the checked prompt was read in {len(served)} slots "
                   f"for {[len(s['at']) for s in served]} decode steps")
    layers = (model.window_layers[-1], model.full_layers[-1])
    errors, chunk_errors, attn_errors, scale = compare(
        served, n, *reference_prompt(run, params, prompt + tokens[:-1], n),
        layers)
    more, numbers = judge(run.traffic, errors, chunk_errors, attn_errors)
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
        f"{run.traffic['logit_tolerance_single']}); attention output of "
        f"layers {layers} (window, full), share of its largest magnitude: "
        f"median {numbers['attn_window_check_ratio']:.4f} / "
        f"{numbers['attn_full_check_ratio']:.4f} (tolerance "
        f"{run.traffic['attn_tolerance']}), max "
        f"{numbers['attn_window_check_ratio_max']:.4f} / "
        f"{numbers['attn_full_check_ratio_max']:.4f} (tolerance "
        f"{run.traffic['attn_tolerance_single']})")
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
        raise SystemExit("runners/serve_window_moe.py: backlog traffic only")
    slots, page = int(tr["slots"]), int(tr["page_size"])
    vocab = cfg["vocab_size"]
    held = tuple(cfg["held_experts"])

    model, params, ccfg, fns, make_pools = build(run)
    from apex_tpu.models.afmoe import COUNTER_NAMES
    peak("weights")
    with run.phase("reference_check"):
        # the served side first: its pools are gone (donated, then
        # dropped with the batcher) before the reference takes its room
        served = served_check(run, fns, ccfg, make_pools(), vocab)
        peak("served check")            # every timed program has run
        why, checked = verdict(run, model, params, slots, *served)
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

    drv = ClassCountingDriver(run, batcher, vocab, Request,
                              names=COUNTER_NAMES)
    source = MixedBacklog(tr, vocab, run.seed)
    trace_s = float(tr.get("trace_seconds", 3.0))

    def refill():
        while len(drv.queue) < slots:
            generation = source.next_generation()
            if not drv.budget:
                run.note(f"first generation (pre-aged): "
                         f"{traffic_gen.describe(generation)}; prompts in "
                         f"order {[len(p.prompt) - p.aged_tokens for p in generation]}")
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
    overwritten_before = dict(batcher.cache.overwritten_pages)
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
    counters.update(drv.class_counters(t_open, t1))
    counters.update({
        f"pages_overwritten_{k}": v - overwritten_before[k]
        for k, v in batcher.cache.overwritten_pages.items()})
    # every run walks the same schedule, so the nominal close falls at
    # the same place in it; ``warm_pumps`` shifts the window along the
    # schedule so that the close falls INSIDE a pump (PERF.md section 6,
    # PR 28: a close on a pump's return lets the clock's wobble decide
    # whether one more pump is counted)
    returns = [t - t_close for t, _ in drv.boundaries if t >= t_open]
    counters["close_after_return_s"] = -returns[-2]
    counters["return_after_close_s"] = returns[-1]
    if "served check" in peaks:
        # the SERVED program's peak: read when every timed program had
        # run at the window's shapes and before the float32 reference
        # took its room; the window's own boundaries never held more
        counters["served_peak_hbm_gb"] = max(
            peaks["served check"], drv.bytes_in_use) / 1e9
    n_window = len(model.window_layers)
    counted = drv.counted_between(t_open, t1)
    counters.update({"window_" + k: v for k, v in derived_counters(
        counted, len(held), n_window).items()})
    # the per-layer metrics read the TRACED stretch's counts where there
    # is one (the device times they are set against come from it)
    tracer = run.tracer
    if tracer.t_started is not None:
        counted = drv.counted_between(
            tracer.t_started, tracer.t_stopped or math.inf) or counted
    counters.update(derived_counters(counted, len(held), n_window))
    if run.trace:
        # the compiled text of the decode program, so that its
        # operations can be read by scope
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
             f"steps and {counters['prefill_chunks']} prefill chunks; window "
             f"layers read {counters.get('window_attn_window_read_share', 0):.1f}"
             f" % of the context rows; the nominal close fell "
             f"{counters['close_after_return_s']:.3f} s after a pump's "
             f"return and {counters['return_after_close_s']:.3f} s before "
             f"the next; harness time between a pump's return and the next "
             f"call: mean {counters['host_gap_mean_ms']:.3f} ms, max "
             f"{counters['host_gap_max_ms']:.3f} ms; {len(failed)} failed")
    counters.update(checked, held_experts=len(held),
                    layers=ccfg.num_layers, expert_layers=model.n_moe,
                    window_layers=n_window)
    return {"t_open": t_open, "correct": not why, "why_incorrect": why,
            "attempted": len(finished), "failed": len(failed),
            "compiled_in_window": dict(compiled),
            "end_to_end": {"serve_tokens_per_s": counters["tokens_per_s"]},
            "counters": counters}
