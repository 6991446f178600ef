"""Runner for traffic of kinds ``backlog`` and ``openloop``: the paged
server, driven one ``pump`` at a time.

The served path is the program's own: ``GPTModel.decode_fns`` ->
``PagedKVCache`` / ``init_pools`` -> ``ContinuousBatcher.pump``.  The
harness owns the queue and the clock.  Every ``pump`` return is a
harvest boundary, the only moment at which tokens reach the host, so:

- a throughput is the generated tokens committed by the pumps from the
  first boundary inside the window to the last, over the time between
  those two boundaries;
- a latency is taken as a client sees it, at the ``pump`` return at
  which ``progress()`` or ``completions`` first shows the token, and
  from the request's DUE time: before each ``pump`` every request whose
  due time has passed is released into the queue, so a request waits for
  the boundary exactly as it would behind the batcher's own loop, and
  that wait is inside its time to first token.

``backlog`` keeps every slot full from a pre-aged first generation (see
``traffic.Backlog``); ``openloop`` runs its arrival process as a ramp
before the window, measures the requests that fall DUE inside the
window wherever they finish, and drains them up to a stated grace of the
server's own time (the seconds the tracer's stop blocks the host are not
counted), after which an unfinished one counts as failed.
"""

from __future__ import annotations

import collections
import itertools
import math
import time

import numpy as np

import timing
import traffic as traffic_gen
from reference import gpt as reference
from runners.train import padded_vocab

CHECK_TOKENS = 480      # the prompt the served logits are checked on


def _logits_check(run, model, fns, params, mesh, ccfg, pools, prompt):
    """One prompt through the SERVED path (the compiled prefill writes
    its first L-1 tokens into the paged cache, one paged decode step
    reads them back through the decode kernel) against the reference's
    full forward on the same L tokens: last-position logits.  Returns
    (pools, max |difference|, max |reference logit|)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.serving.kv_cache import PagedKVCache
    from apex_tpu.transformer.tensor_parallel import (
        gather_from_tensor_model_parallel_region as gather,
    )

    cfg = run.config
    L = len(prompt)
    cache = PagedKVCache(ccfg)
    cache.admit(0, L)
    padded = np.zeros((1, int(run.traffic["max_prompt_len"])), np.int32)
    padded[0, :L] = prompt
    pools, _ = fns.prefill(
        pools, jnp.asarray(padded), jnp.int32(L - 1),
        jnp.asarray(cache.page_table[0]), jax.random.PRNGKey(0))
    slot0 = np.arange(ccfg.max_seqs) == 0

    def decode_logits(p, pools, tok, pos, table):
        logits, _ = model.decode_step(
            p, tok, pos, jnp.asarray(slot0), table, pools)
        return gather(logits)[0]

    served = jax.jit(jax.shard_map(
        decode_logits, mesh=mesh,
        in_specs=(fns.param_specs, fns.pool_specs, P(), P(), P()),
        out_specs=P()))(
        params, pools,
        jnp.where(slot0, prompt[L - 1], 0).astype(jnp.int32),
        jnp.where(slot0, L - 1, 0).astype(jnp.int32),
        jnp.asarray(cache.page_table))
    ref = reference.logits(
        reference.from_stacked(params), np.asarray(prompt)[None, :],
        heads=cfg["n_head"], layers=cfg["n_layer"],
        eps=cfg["layer_norm_epsilon"])[0, L - 1]
    served = np.asarray(served, np.float32)
    ref = np.asarray(ref, np.float32)
    return (pools, float(np.max(np.abs(served - ref))),
            float(np.max(np.abs(ref))))


class Driver:
    """Pumps the batcher and keeps the books at every boundary."""

    def __init__(self, run, batcher, vocab: int, request_type):
        self.run, self.batcher, self.vocab = run, batcher, vocab
        self.request_type = request_type    # the program's ``Request``
        self.queue: collections.deque = collections.deque()
        self.boundaries = []            # (t, generated tokens committed)
        self.gauges = []                # (t, live slots, ctx tokens, share)
        self.host_gaps = []             # pump return -> next pump call
        self.t_first, self.t_last, self.t_left_queue = {}, {}, {}
        self.budget = {}                # uid -> tokens asked for
        self.n_tokens = {}              # uid -> tokens delivered
        self.invalid = set()
        self._done_tokens = 0
        self._seen = len(batcher.completions)   # warm-up requests
        self._t_ret = None

    def submit(self, planned) -> None:
        self.budget[planned.uid] = planned.new_tokens
        self.queue.append(self.request_type(
            uid=planned.uid, prompt=[int(t) for t in planned.prompt],
            max_new_tokens=planned.new_tokens))

    def pump(self) -> float:
        """One ``pump``; returns the time of its return."""
        t_call = time.perf_counter()
        if self._t_ret is not None:
            self.host_gaps.append(t_call - self._t_ret)
        waiting = [r.uid for r in self.queue]
        with self.run.span("bench.pump"):
            self.batcher.pump(self.queue)
        t = self._t_ret = time.perf_counter()
        with self.run.span("bench.books"):
            # admitted entries are popped: a request's queue wait ends at
            # the CALL of the pump that admitted it (its return is
            # already the harvest that shows the first token)
            still = {r.uid for r in self.queue}
            for uid in waiting:
                if uid not in still:
                    self.t_left_queue[uid] = t_call
            in_flight = 0
            for uid, toks in self.batcher.progress().items():
                if uid in self.budget and toks:
                    in_flight += len(toks)
                    self.t_first.setdefault(uid, t)
            done = self.batcher.completions
            retired = len(done) - self._seen
            for uid in itertools.islice(done, self._seen, None):
                self._seen += 1
                toks = done[uid].tokens
                self._done_tokens += len(toks)
                self.n_tokens[uid] = len(toks)
                self.t_first.setdefault(uid, t)
                self.t_last[uid] = t
                if len(toks) != self.budget[uid] or not all(
                        0 <= x < self.vocab for x in toks):
                    self.invalid.add(uid)
            self.boundaries.append((t, self._done_tokens + in_flight))
            cache = self.batcher.cache
            page = cache.config.page_size
            lengths = cache.lengths
            holding = int(np.sum(-(-lengths // page)))
            allocated = (cache.config.num_pages - 1
                         - cache.allocator.num_free)
            # slots that decoded in this pump's window: those still
            # live plus those it retired at its harvest
            self.gauges.append((
                t, self.batcher.live_slots + retired, int(lengths.sum()),
                holding / allocated if allocated else 0.0))
        return t

    def slept(self) -> None:
        """The harness waited for an arrival: that is no gap of its own
        making between two pumps."""
        self._t_ret = None

    # ------------------------------------------------------- reductions
    def window_counters(self, t_open: float, seconds: float) -> dict:
        owned = timing.window_boundaries(self.boundaries, t_open, seconds)
        t0, t1 = owned[0][0], owned[-1][0]
        gauges = [g for g in self.gauges if t0 <= g[0] <= t1]
        traced = [g for g in self.gauges
                  if self.run.tracer.t_started is not None
                  and g[0] >= self.run.tracer.t_started
                  and g[0] <= (self.run.tracer.t_stopped or math.inf)]
        gaps = self.host_gaps
        return {
            "tokens_per_s": timing.rate_between(owned),
            "pumps": len(owned) - 1,
            "boundary_span_s": t1 - t0,
            "slots_live_mean": timing.time_weighted_mean(
                [(g[0], g[1]) for g in gauges]),
            "pages_in_use_share": 100.0 * sum(g[3] for g in gauges[1:])
            / max(len(gauges) - 1, 1),
            "live_context_tokens_mean": sum(g[2] for g in gauges)
            / max(len(gauges), 1),
            "traced_live_context_tokens_mean":
                sum(g[2] for g in traced) / len(traced) if traced else None,
            "host_gap_mean_ms": 1e3 * sum(gaps) / max(len(gaps), 1),
            "host_gap_max_ms": 1e3 * max(gaps, default=0.0),
        }

    def tpot_ms(self, uids) -> list:
        return [1e3 * (self.t_last[u] - self.t_first[u])
                / (self.n_tokens[u] - 1)
                for u in uids if u in self.t_last and self.n_tokens[u] > 1]


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving.kv_cache import (
        KVCacheConfig, PagedKVCache, init_pools,
    )
    from apex_tpu.serving.serve import ContinuousBatcher, Request
    from apex_tpu.transformer import parallel_state

    cfg, tr = run.config, run.traffic
    tp = len(run.devices)
    vocab_rows = padded_vocab(cfg["vocab_size"], tp)
    slots, page = int(tr["slots"]), int(tr["page_size"])
    pages_per_seq = int(tr["pages_per_seq"])
    max_prompt = int(tr["max_prompt_len"])
    why = []

    with run.phase("weights_on_device"):
        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=tp)
        model = GPTModel(GPTConfig(
            vocab_size=vocab_rows, num_layers=cfg["n_layer"],
            hidden_size=cfg["n_embd"], num_attention_heads=cfg["n_head"],
            ffn_hidden_size=cfg["n_inner"],
            max_position_embeddings=cfg["n_positions"],
            layernorm_epsilon=cfg["layer_norm_epsilon"],
            policy=amp.initialize("O5").policy))
        on_mesh = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

        def weights(key):
            """Random weights from the seed; the rows that pad the
            published vocabulary are zero, as in a checkpoint converted
            from the 50257-row model (random pad rows would now and then
            win the argmax and the server would emit an id no tokenizer
            has)."""
            p = model.init(key)
            w = p["embedding"]["weight"]
            p["embedding"]["weight"] = w.at[cfg["vocab_size"]:].set(0)
            return p

        # one jitted call from the seed, in the type they are served in
        params = jax.jit(weights, out_shardings=on_mesh(
            model.param_specs()))(jax.random.PRNGKey(run.seed & 0x7FFFFFFF))
        jax.block_until_ready(params)
    with run.phase("steps_and_pool"):
        ccfg = KVCacheConfig(
            num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
            head_dim=cfg["n_embd"] // cfg["n_head"],
            num_pages=1 + slots * pages_per_seq, page_size=page,
            max_seqs=slots, pages_per_seq=pages_per_seq, dtype=jnp.bfloat16)
        fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=max_prompt,
                               weight_dtype=None)
        pools = jax.jit(lambda: init_pools(ccfg),
                        out_shardings=on_mesh(fns.pool_specs))()
    with run.phase("reference_check"):
        prompt = traffic_gen.zipf_tokens(
            traffic_gen.rng_for(run.seed, 4), cfg["vocab_size"],
            min(CHECK_TOKENS, max_prompt))
        pools, diff, scale = _logits_check(
            run, model, fns, params, mesh, ccfg, pools, prompt)
    # bf16 weights, activations and cache against a float32 reference on
    # the same weights: a few bf16 steps of the largest logit (measured,
    # PERF.md).  An 8-bit weight or cache path, a wrong page or a wrong
    # position moves the logits by far more than this.
    tolerance = float(tr["logit_tolerance"])
    ratio = diff / max(scale, 1.0)
    run.note(f"reference: served prefill + paged decode vs float32 "
             f"reference on {len(prompt)} tokens, last-position logits: max "
             f"|diff| {diff:.4f}, max |logit| {scale:.3f}, ratio "
             f"{ratio:.4f} (tolerance {tolerance})")
    if not (math.isfinite(ratio) and ratio <= tolerance):
        why.append(f"served logits differ from the reference by {ratio} "
                   f"of the largest logit")

    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), pools,
        max_prompt_len=max_prompt)
    with run.phase("warm_window_lengths"):
        # every window length 1..harvest_every stacks another shape
        warm_rng = traffic_gen.rng_for(run.seed, 5)
        for k in range(1, batcher.harvest_every + 1):
            batcher.run([Request(
                uid=("warm", k), max_new_tokens=k + 1,
                prompt=[int(t) for t in traffic_gen.zipf_tokens(
                    warm_rng, cfg["vocab_size"], page)])])

    drv = Driver(run, batcher, cfg["vocab_size"], Request)
    kind = tr["kind"]
    trace_s = float(tr.get("trace_seconds", 3.0))
    if kind == "backlog":
        source = traffic_gen.Backlog(tr, cfg["vocab_size"], run.seed)

        def refill():
            while len(drv.queue) < slots:
                generation = source.next_generation()
                if not drv.budget:
                    run.note(f"first generation (pre-aged): "
                             f"{traffic_gen.describe(generation)}")
                for planned in generation:
                    drv.submit(planned)

        with run.phase("fill_slots"):
            for _ in range(int(tr["warm_pumps"])):
                refill()
                drv.pump()
        before = run.clock.snapshot()
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
        counters = drv.window_counters(t_open, run.seconds)
        t1 = drv.boundaries[-1][0]
        finished = [u for u, t in drv.t_last.items() if t_open <= t <= t1]
        failed = [u for u in finished if u in drv.invalid]
        counters["tpot_p50_ms"] = timing.percentile(drv.tpot_ms(finished), 50)
        counters["completions"] = len(finished)
        end_to_end = {"serve_tokens_per_s": counters["tokens_per_s"]}
        attempted = len(finished)
    elif kind == "openloop":
        plan = traffic_gen.openloop_schedule(
            tr, cfg["vocab_size"], run.seed, run.seconds)
        measured = [p for p in plan if p.measured]
        run.note(f"plan: rate {tr['rate_per_s']}/s, ramp "
                 f"{traffic_gen.describe([p for p in plan if not p.measured])}"
                 f", measured {traffic_gen.describe(measured)}")
        ramp, grace = float(tr["ramp_seconds"]), float(tr["grace_seconds"])
        t_zero = time.perf_counter()
        t_open, t_close = t_zero + ramp, t_zero + ramp + run.seconds
        run.tracer.arm(t_close - trace_s, t_close)
        due = {p.uid: t_zero + p.due_s for p in plan}
        before, nxt, late = None, 0, []
        while True:
            now = time.perf_counter()
            if before is None and now >= t_open:
                before = run.clock.snapshot()
            while nxt < len(plan) and due[plan[nxt].uid] <= now:
                late.append(now - due[plan[nxt].uid])
                drv.submit(plan[nxt])
                nxt += 1
            if not drv.queue and batcher.live_slots == 0:
                if nxt >= len(plan):
                    break                       # everything is served
                with run.span("bench.idle"):
                    time.sleep(max(due[plan[nxt].uid]
                                   - time.perf_counter(), 0.0))
                drv.slept()
                continue
            now = drv.pump()
            run.tracer.poll(now)
            if now >= t_close and all(p.uid in drv.t_last for p in measured):
                break
            # the drain's grace is the server's own time: the seconds
            # the tracer's stop blocked the host are not counted
            if now >= t_close + grace + run.tracer.stop_s:
                break
        run.tracer.stop()
        compiled = run.clock.snapshot() - (before or run.clock.snapshot())
        run.add_setup("ramp", ramp)
        counters = drv.window_counters(t_open, run.seconds)
        t_end = drv.boundaries[-1][0]
        unfinished = [p.uid for p in measured if p.uid not in drv.t_last]
        failed = unfinished + [p.uid for p in measured
                               if p.uid in drv.invalid]
        # a request that never got its token waited at least to the end
        ttft = [drv.t_first.get(p.uid, t_end) - due[p.uid] for p in measured]
        tpot = drv.tpot_ms([p.uid for p in measured])
        waits = [drv.t_left_queue[p.uid] - due[p.uid] for p in measured
                 if p.uid in drv.t_left_queue]
        counters.update(
            ttft_p50_s=timing.percentile(ttft, 50),
            queue_wait_p50_s=timing.percentile(waits, 50),
            release_lateness_p50_s=timing.percentile(late, 50),
            drained_after_close_s=t_end - t_close,
            unfinished=len(unfinished))
        run.note(f"drain: last boundary {t_end - t_close:.3f} s after the "
                 f"close (drained_after_close_s, the stop included); the "
                 f"tracer's stop blocked the host {run.tracer.stop_s:.3f} s, "
                 f"which the deadline of close + {grace} s does not count; "
                 f"{len(unfinished)} measured request(s) unfinished")
        end_to_end = {"ttft_p90_s": timing.percentile(ttft, 90),
                      "tpot_p50_ms": timing.percentile(tpot, 50)}
        attempted = len(measured)
    else:
        raise SystemExit(f"runners/serve.py: unknown traffic kind {kind!r}")

    if failed:
        why.append(f"{len(failed)} request(s) unfinished or with a wrong "
                   f"token count or a token outside the vocabulary")
    run.note(f"window: {counters['pumps']} pumps over "
             f"{counters['boundary_span_s']:.3f} s between boundaries, "
             f"{counters['tokens_per_s']:.3f} generated tokens/s, slots live "
             f"mean {counters['slots_live_mean']:.2f}; harness time between "
             f"a pump's return and the next call: mean "
             f"{counters['host_gap_mean_ms']:.3f} ms, max "
             f"{counters['host_gap_max_ms']:.3f} ms; {attempted} attempted, "
             f"{len(failed)} failed")
    counters.update(heads_per_chip=cfg["n_head"] // tp,
                    head_dim=cfg["n_embd"] // cfg["n_head"],
                    logits_check_ratio=ratio)
    return {"t_open": t_open, "correct": not why, "why_incorrect": why,
            "attempted": attempted, "failed": len(failed),
            "compiled_in_window": dict(compiled),
            "end_to_end": end_to_end, "counters": counters}
