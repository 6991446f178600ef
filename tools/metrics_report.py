"""metrics_report: telemetry JSONL → run summary.

Reads the record stream a :class:`apex_tpu.telemetry.MetricsLogger`
appends (``--metrics-jsonl`` on the example trainers; schema in
docs/observability.md) and reports what a final tokens/s number cannot:

- **throughput/MFU trajectory** — every per-flush ``throughput``
  record, plus headline stats (best / mean / final window), in the
  same ``metric``/``value``/``unit`` shape the ``BENCH_*.json``
  records use so the two are directly comparable (``--bench`` diffs
  against one);
- **step-time breakdown** — host-side phase timings (the logger's
  ``timing()`` meters: data / checkpoint / ...) as per-step
  milliseconds next to the measured ms/step, so "the input pipeline
  ate the speedup" is visible in one table;
- **event timeline** — every subsystem event (checkpoint saves /
  verify outcomes / guard escalations / GC / watchdog stalls /
  comm-bucket estimates) with run-relative timestamps and per-kind
  counts, interleaved with the step indices they landed between;
- **serving summary** — when the stream came from a serving run
  (``apex_tpu/serving/serve.py``'s ``tlm.prefill``/``tlm.decode``
  ``span`` records + ``request_done``/``prefix_hit`` events):
  per-window decode tokens/s, time-to-first-token stats, inter-token
  latency percentiles, request completion counts by reason, chunked-
  prefill progress (``prefill_chunk`` spans), and the prefix-cache
  scoreboard (hit rate, pages shared, prefill tokens skipped);
- **fault / recovery ledger** — when the stream came from a fleet run
  with the fault-tolerance tier engaged: replica faults and
  quarantines, migrations by cause, deadline misses (retried vs
  terminal), hedge spawns/wins/losses, brownout transitions with the
  pressure that drove them, journal replays, and per-class SLO
  attainment (completions not cut off at their deadline).

Usage::

    python tools/metrics_report.py run_metrics.jsonl
    python tools/metrics_report.py run_metrics.jsonl --json out.json
    python tools/metrics_report.py run_metrics.jsonl --bench bench_line.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def load_records(path: str) -> List[dict]:
    """Parse a metrics JSONL file; malformed lines (a crashed writer's
    torn tail) are counted, not fatal."""
    records, bad = [], 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                bad += 1
                continue
            if isinstance(rec, dict):
                records.append(rec)
    if bad:
        print(f"note: skipped {bad} malformed line(s)", file=sys.stderr)
    return records


def _stats(xs: List[float], better=max) -> Dict[str, float]:
    return {
        "mean": sum(xs) / len(xs),
        "best": better(xs),  # max for rates, min for ms/step
        "final": xs[-1],
    }


def _percentile(xs: List[float], q: float) -> float:
    """Nearest-rank percentile (no numpy dependency here)."""
    s = sorted(xs)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[idx]


def summarize_serving(records: List[dict]) -> Optional[Dict[str, Any]]:
    """The serving section: decode throughput per harvest window, TTFT,
    and inter-token latency from the ``span``/``request_done`` event
    stream ``ContinuousBatcher`` emits.  None when the stream holds no
    serving records (training runs keep their report unchanged)."""
    spans = [r for r in records
             if r.get("kind") == "event" and r.get("event") == "span"]
    done = [r for r in records
            if r.get("kind") == "event"
            and r.get("event") == "request_done"]
    hits = [r for r in records
            if r.get("kind") == "event"
            and r.get("event") == "prefix_hit"]
    decode = [r for r in spans if r.get("span") == "decode"
              and r.get("steps")]
    prefill = [r for r in spans if r.get("span") == "prefill"]
    chunks = [r for r in spans if r.get("span") == "prefill_chunk"]
    if not (decode or prefill or done):
        return None
    out: Dict[str, Any] = {}
    if decode:
        windows = []
        itl: List[float] = []       # per-window mean inter-token s
        wgbs: List[float] = []      # per-window weight-stream GB/s
        for r in decode:
            dur = float(r.get("dur_s", 0.0))
            steps = int(r.get("steps", 0))
            toks = int(r.get("tokens", 0))
            w = {"steps": steps, "tokens": toks,
                 "dur_s": round(dur, 6)}
            if dur > 0 and toks:
                w["tokens_per_sec"] = round(toks / dur, 1)
            if dur > 0 and steps:
                itl.append(dur / steps)
            # every decode step streams the whole weight pool once
            # (serve.py stamps the per-step bytes on the span), so the
            # window's achieved weight bandwidth is steps * bytes / dur
            # — at small batch this IS the decode roofline, and the
            # int8/int4 pools shrink the numerator, not the rate
            wb = r.get("weight_bytes")
            if dur > 0 and steps and wb:
                g = round(steps * float(wb) / dur / 1e9, 6)
                w["weight_stream_gbs"] = g
                wgbs.append(g)
            windows.append(w)
        out["decode_windows"] = windows
        rates = [w["tokens_per_sec"] for w in windows
                 if "tokens_per_sec" in w]
        if rates:
            out["decode_tokens_per_sec"] = _stats(rates)
        wdts = {r["weight_dtype"] for r in decode
                if r.get("weight_dtype")}
        if wdts:
            out["weight_dtype"] = (sorted(wdts)[0] if len(wdts) == 1
                                   else sorted(wdts))
        # the tensor-parallel degree rides the decode spans exactly
        # like weight_dtype; weight_bytes is already PER CHIP (gpt.py
        # stamps each chip's own pool slice), so the GB/s above is the
        # per-chip stream without further division
        tps = {int(r["tp"]) for r in decode if r.get("tp")}
        if tps:
            out["tp"] = (sorted(tps)[0] if len(tps) == 1
                         else sorted(tps))
        if wgbs:
            out["weight_stream_gbs"] = _stats(wgbs)
        if itl:
            # the harvest window quantizes this to window-mean
            # granularity (serve.py docstring) — percentiles are over
            # per-window means, honest about what was measured
            out["inter_token_latency_ms"] = {
                "p50": round(_percentile(itl, 50) * 1e3, 3),
                "p90": round(_percentile(itl, 90) * 1e3, 3),
                "p99": round(_percentile(itl, 99) * 1e3, 3),
                "mean": round(sum(itl) / len(itl) * 1e3, 3),
            }
    if prefill:
        out["prefill_spans"] = len(prefill)
        ptoks = [int(r["tokens"]) for r in prefill if "tokens" in r]
        if ptoks:
            out["prefill_tokens"] = sum(ptoks)
    if chunks:
        cms = [float(r["dispatch_s"]) * 1e3 for r in chunks
               if "dispatch_s" in r]
        out["prefill_chunks"] = {
            "count": len(chunks),
            "tokens": sum(int(r.get("tokens", 0)) for r in chunks),
        }
        if cms:
            out["prefill_chunks"]["mean_ms"] = round(
                sum(cms) / len(cms), 3)
            out["prefill_chunks"]["max_ms"] = round(max(cms), 3)
    if hits:
        # the prefix-cache scoreboard: one prefix_hit event lands per
        # chunked admission (matched_tokens == 0 on a miss)
        matched = [int(r.get("matched_tokens", 0)) for r in hits]
        out["prefix_cache"] = {
            "admissions": len(hits),
            "hits": sum(1 for m in matched if m > 0),
            "hit_rate": round(
                sum(1 for m in matched if m > 0) / len(hits), 4),
            "matched_tokens": sum(matched),
            "pages_shared": sum(
                int(r.get("shared_pages", 0)) for r in hits),
            "prefill_tokens_skipped": sum(
                int(r.get("tokens_skipped", 0)) for r in hits),
            "pages_copied": sum(
                1 for r in hits if r.get("copied")),
        }
    spec = [r for r in records
            if r.get("kind") == "event"
            and r.get("event") == "spec_accept"]
    if spec:
        # the speculation scoreboard: one spec_accept event per verify
        # step (emitted from the commit resolve the speculative window
        # already performs — no extra host syncs behind it)
        drafted = sum(int(r.get("drafted", 0)) for r in spec)
        accepted = sum(int(r.get("accepted", 0)) for r in spec)
        committed = sum(int(r.get("committed", 0)) for r in spec)
        slot_steps = sum(len(r.get("commits", [])) for r in spec)
        offramp = sum(int(r.get("offramp", 0)) for r in spec)
        # commits-per-slot-step doubles as the committed TREE DEPTH
        # histogram (a commit of n is a depth-(n-1) accepted path plus
        # its correction/bonus draw)
        hist: Dict[str, int] = {}
        for r in spec:
            for nc in r.get("commits", []):
                hist[str(int(nc))] = hist.get(str(int(nc)), 0) + 1
        # draft-model host cost: the speculative decode spans stamp
        # the wall seconds spent inside draft() (dur_s includes it, so
        # the ratio is the draft's fraction of the serving wall)
        draft_wall = sum(float(r.get("draft_s", 0.0)) for r in decode)
        spec_wall = sum(float(r.get("dur_s", 0.0)) for r in decode)
        by_source: Dict[str, Dict[str, Any]] = {}
        for r in spec:
            for src, rec in (r.get("by_source") or {}).items():
                tot = by_source.setdefault(
                    src, {"drafted": 0, "accepted": 0})
                tot["drafted"] += int(rec.get("drafted", 0))
                tot["accepted"] += int(rec.get("accepted", 0))
        for src, tot in by_source.items():
            if tot["drafted"]:
                tot["hit_rate"] = round(
                    tot["accepted"] / tot["drafted"], 4)
        out["speculation"] = {
            "verify_steps": len(spec),
            "drafted": drafted,
            "accepted": accepted,
            "committed": committed,
            # tokens committed per slot per verify step (1 = the plain
            # decode rate; k+1 = a fully accepted draft + bonus)
            "accepted_per_step_hist": hist,
            "committed_per_slot_step": (
                round(committed / slot_steps, 4) if slot_steps else None),
            # drafted rows the verify pass computed but threw away —
            # the price of a miss, what the k-selection trade bounds
            "wasted_verify_fraction": (
                round((drafted - accepted) / drafted, 4)
                if drafted else None),
            # commits that rode a non-spine tree branch — every one is
            # a token the chain verifier would have rejected
            "offramp_commits": offramp,
            "draft_wall_s": round(draft_wall, 6),
            "draft_wall_fraction": (
                round(draft_wall / spec_wall, 4) if spec_wall > 0 else None),
            "by_source": by_source,
        }
    if done:
        reasons: Dict[str, int] = {}
        ttfts = []
        for r in done:
            reasons[str(r.get("reason", "?"))] = \
                reasons.get(str(r.get("reason", "?")), 0) + 1
            if isinstance(r.get("ttft_s"), (int, float)):
                ttfts.append(float(r["ttft_s"]))
        out["requests"] = {"completed": len(done), "by_reason": reasons}
        # exact TTFT: the span from each request_admitted event to the
        # prefill span that sampled its first token, both wall-clock
        # event timestamps — NOT the harvest-quantized ttft_s the
        # Completion carries (the first token exists on device when the
        # prefill span lands; the harvest merely SURFACES it later).
        # Correlation is by slot: an admission owns its slot until its
        # prefill completes, so the next prefill span on that slot is
        # its own.
        exact = _exact_ttfts(records)
        source = "exact" if exact else "completion"
        if not exact:
            exact = ttfts          # old streams without admit events
        if exact:
            out["ttft_s"] = {
                "p50": round(_percentile(exact, 50), 6),
                "p95": round(_percentile(exact, 95), 6),
                "mean": round(sum(exact) / len(exact), 6),
                "max": round(max(exact), 6),
                "source": source,
            }
    return out


def _exact_ttfts(records: List[dict]) -> List[float]:
    """Admission-to-first-token spans from exact event timestamps:
    walk the stream in order, pairing each ``request_admitted`` with
    the next ``span=prefill`` event on the same slot."""
    pending: Dict[Any, float] = {}          # slot -> admit t
    exact: List[float] = []
    for r in records:
        if r.get("kind") != "event" or "t" not in r:
            continue
        if r.get("event") == "request_admitted" and "slot" in r:
            pending[r["slot"]] = float(r["t"])
        elif (r.get("event") == "span" and r.get("span") == "prefill"
                and r.get("slot") in pending):
            exact.append(float(r["t"]) - pending.pop(r["slot"]))
    return exact


def summarize_fleet(records: List[dict]) -> Optional[Dict[str, Any]]:
    """The fleet section: per-class TTFT/ITL percentiles from the
    ``trace_request`` records ``tools/load_gen.py``'s replay emits
    (arrival-anchored — queue wait included), plus the routing /
    rejection / migration ledger from the router's own events.  None
    when the stream holds no fleet records."""
    trace = [r for r in records
             if r.get("kind") == "event"
             and r.get("event") == "trace_request"]
    routed = [r for r in records
              if r.get("kind") == "event"
              and r.get("event") == "request_routed"]
    if not (trace or routed):
        return None
    out: Dict[str, Any] = {}
    if routed:
        per: Dict[str, int] = {}
        for r in routed:
            name = str(r.get("replica", "?"))
            per[name] = per.get(name, 0) + 1
        out["routed"] = per
        out["affinity_routed"] = sum(
            1 for r in routed if r.get("affinity", 0))
    for kind, key in (("request_rejected", "rejected"),
                      ("request_migrated", "migrated"),
                      ("replica_dead", "replicas_dead")):
        n = sum(1 for r in records if r.get("kind") == "event"
                and r.get("event") == kind)
        if n:
            out[key] = n
    if trace:
        done = [r for r in trace if "reason" in r]
        out["trace"] = {
            "requests": len(trace),
            "completed": len(done),
            "lost": sum(1 for r in trace if r.get("lost")),
        }
        by_class: Dict[str, Any] = {}
        for name in sorted({str(r.get("slo")) for r in done}):
            rs = [r for r in done if str(r.get("slo")) == name]
            ttfts = [float(r["ttft_s"]) for r in rs
                     if isinstance(r.get("ttft_s"), (int, float))]
            itls = [float(r["itl_ms"]) for r in rs
                    if isinstance(r.get("itl_ms"), (int, float))]
            c: Dict[str, Any] = {"n": len(rs)}
            if ttfts:
                c["ttft_s"] = {
                    "p50": round(_percentile(ttfts, 50), 6),
                    "p99": round(_percentile(ttfts, 99), 6),
                }
            if itls:
                c["itl_ms"] = {
                    "p50": round(_percentile(itls, 50), 3),
                    "p99": round(_percentile(itls, 99), 3),
                }
            by_class[name] = c
        out["by_class"] = by_class
    return out


def summarize_faults(records: List[dict]) -> Optional[Dict[str, Any]]:
    """The fault/recovery section: what the fleet's fault-tolerance
    tier did — replica faults/quarantines, migrations by cause,
    deadline misses split into retried vs terminal, the hedge
    scoreboard, brownout transitions, and journal replays — plus
    per-class SLO attainment over the ``trace_request`` stream (the
    fraction of completions NOT cut off at their deadline).  None when
    the stream holds none of those events."""
    ev = {}
    for r in records:
        if r.get("kind") == "event":
            ev.setdefault(r.get("event"), []).append(r)
    faults = ev.get("replica_fault", [])
    quar = ev.get("replica_quarantined", [])
    misses = ev.get("deadline_miss", [])
    hedges = ev.get("hedge_spawn", [])
    hwins = ev.get("hedge_win", [])
    hlosses = ev.get("hedge_loss", [])
    brown = ev.get("brownout", [])
    replays = ev.get("journal_replayed", [])
    migr = ev.get("request_migrated", [])
    if not (faults or quar or misses or hedges or brown or replays):
        return None
    out: Dict[str, Any] = {}
    if faults:
        per: Dict[str, int] = {}
        for r in faults:
            name = str(r.get("replica", "?"))
            per[name] = per.get(name, 0) + 1
        out["replica_faults"] = {"count": len(faults), "by_replica": per}
    if quar:
        out["quarantined"] = [
            {"replica": r.get("replica"), "cause": r.get("cause")}
            for r in quar]
    if migr:
        by_cause: Dict[str, int] = {}
        for r in migr:
            c = str(r.get("cause", "replica_dead"))
            by_cause[c] = by_cause.get(c, 0) + 1
        out["migrations"] = {"count": len(migr), "by_cause": by_cause}
    if misses:
        retried = sum(1 for r in misses if r.get("retry"))
        out["deadline_misses"] = {
            "count": len(misses),
            "retried": retried,
            "terminal": len(misses) - retried,
        }
    if hedges or hwins or hlosses:
        out["hedging"] = {"spawned": len(hedges), "wins": len(hwins),
                          "losses": len(hlosses)}
    if brown:
        out["brownout"] = {
            "transitions": len(brown),
            "max_level": max(int(r.get("to_level", 0)) for r in brown),
            "ladder": [
                {"from": r.get("from_level"), "to": r.get("to_level"),
                 "free_page_frac": r.get("free_page_frac"),
                 "queue_depth": r.get("queue_depth")}
                for r in brown],
        }
    if replays:
        out["journal_replays"] = [
            {k: r.get(k) for k in ("resumed", "completed", "corrupt",
                                   "gapped")}
            for r in replays]
    # per-class SLO attainment over the trace stream: a completion
    # whose reason is "deadline" burned its budget of time — everything
    # else (eos/budget/...) made its SLO window
    trace = [r for r in ev.get("trace_request", []) if "reason" in r]
    if trace:
        att: Dict[str, Any] = {}
        for name in sorted({str(r.get("slo")) for r in trace}):
            rs = [r for r in trace if str(r.get("slo")) == name]
            missed = sum(1 for r in rs if r.get("reason") == "deadline")
            att[name] = {
                "n": len(rs),
                "deadline_missed": missed,
                "attainment": round(1.0 - missed / len(rs), 4),
            }
        out["slo_attainment"] = att
    return out


def summarize_kv_movement(records: List[dict]
                          ) -> Optional[Dict[str, Any]]:
    """The disaggregation/offload section: page-level KV movement.

    Three event streams feed it — ``kv_handoff`` (prefill→decode
    ownership transfers that MOVED pages instead of recomputing),
    ``page_offload`` (index-only prefix pages staged to the host-RAM
    tier instead of dying at eviction), and ``page_faultin`` (offloaded
    pages adopted back into the device pool at admission).  The hit
    rate scores the offload tier against its recompute alternative:
    fault-in walks that found every page they asked for vs walks that
    fell back to prefill.  None when the stream holds none of these."""
    ev: Dict[str, List[dict]] = {}
    for r in records:
        if r.get("kind") == "event":
            ev.setdefault(r.get("event"), []).append(r)
    handoffs = ev.get("kv_handoff", [])
    offloads = ev.get("page_offload", [])
    faults = ev.get("page_faultin", [])
    if not (handoffs or offloads or faults):
        return None
    out: Dict[str, Any] = {}
    if handoffs:
        durs = [float(r["dur_s"]) * 1e3 for r in handoffs
                if isinstance(r.get("dur_s"), (int, float))]
        routes: Dict[str, int] = {}
        for r in handoffs:
            key = f"{r.get('src', '?')}->{r.get('dst', '?')}"
            routes[key] = routes.get(key, 0) + 1
        out["handoffs"] = {
            "count": len(handoffs),
            "pages": sum(int(r.get("pages", 0)) for r in handoffs),
            "wire_bytes": sum(int(r.get("bytes", 0))
                              for r in handoffs),
            "by_route": routes,
        }
        if durs:
            out["handoffs"]["ms"] = {
                "mean": round(sum(durs) / len(durs), 3),
                "max": round(max(durs), 3),
            }
    if offloads:
        out["offload"] = {
            "events": len(offloads),
            "pages": sum(int(r.get("pages", 0)) for r in offloads),
            "wire_bytes": sum(int(r.get("bytes", 0))
                              for r in offloads),
        }
    if faults:
        durs = [float(r["dur_s"]) * 1e3 for r in faults
                if isinstance(r.get("dur_s"), (int, float))]
        misses = sum(1 for r in faults if int(r.get("misses", 0)) > 0)
        out["faultin"] = {
            "events": len(faults),
            "pages": sum(int(r.get("pages", 0)) for r in faults),
            "wire_bytes": sum(int(r.get("bytes", 0)) for r in faults),
            # a walk that missed fell back to recompute for the tail;
            # hit rate = fully-served fault-ins / all fault-in walks
            "chain_misses": misses,
            "hit_rate": round(1.0 - misses / len(faults), 4),
            "prefill_tokens_saved": sum(int(r.get("tokens", 0))
                                        for r in faults),
        }
        if durs:
            out["faultin"]["ms"] = {
                "mean": round(sum(durs) / len(durs), 3),
                "max": round(max(durs), 3),
            }
    return out


def summarize(records: List[dict]) -> Dict[str, Any]:
    """Aggregate one run's records into the report dict."""
    steps = [r for r in records if r.get("kind") == "step"]
    thr = [r for r in records if r.get("kind") == "throughput"]
    meters = [r for r in records if r.get("kind") == "meters"]
    events = [r for r in records if r.get("kind") == "event"]
    t0 = min((r["t"] for r in records if "t" in r), default=0.0)

    out: Dict[str, Any] = {
        "runs": sorted({r["run"] for r in records if "run" in r}),
        "n_records": len(records),
    }

    if steps:
        scalar_keys = sorted(
            k for k in steps[-1]
            if k not in ("t", "kind", "step", "run")
        )
        out["steps"] = {
            "count": len(steps),
            "first": steps[0].get("step"),
            "last": steps[-1].get("step"),
        }
        out["scalars"] = {}
        for k in scalar_keys:
            xs = [float(r[k]) for r in steps
                  if isinstance(r.get(k), (int, float))]
            if xs:
                out["scalars"][k] = {
                    "first": xs[0], "last": xs[-1],
                    "min": min(xs), "max": max(xs),
                }

    if thr:
        tps = [float(r["tokens_per_sec"]) for r in thr
               if "tokens_per_sec" in r]
        msps = [float(r["ms_per_step"]) for r in thr
                if "ms_per_step" in r]
        mfus = [float(r["mfu"]) for r in thr if "mfu" in r]
        out["throughput"] = {
            "windows": [
                {k: (round(v, 4) if isinstance(v, float) else v)
                 for k, v in r.items()
                 if k in ("step", "ms_per_step", "tokens_per_sec", "mfu")}
                for r in thr
            ],
        }
        if tps:
            # the BENCH_*.json-comparable headline (bench reports the
            # best batch's steady-state rate; "best window" is the
            # live-stream analog)
            out["throughput"]["tokens_per_sec"] = _stats(tps)
            out["metric"] = "run_tokens_per_sec"
            out["value"] = round(max(tps), 1)
            out["unit"] = "tokens/s"
        if msps:
            out["throughput"]["ms_per_step"] = _stats(msps, better=min)
        if mfus:
            out["throughput"]["mfu"] = _stats(mfus)

    if meters:
        final = meters[-1]
        breakdown: Dict[str, Any] = {}
        timings = final.get("timings_ms")
        if timings and steps:
            n = max(len(steps), 1)
            breakdown["host_phase_ms_per_step"] = {
                k: round(v / n, 4) for k, v in timings.items()
            }
        if final.get("counters"):
            breakdown["counters"] = final["counters"]
        if final.get("gauges"):
            breakdown["gauges"] = final["gauges"]
        if breakdown:
            out["meters"] = breakdown

    if events:
        counts: Dict[str, int] = {}
        timeline = []
        for r in events:
            kind = r.get("event", "?")
            counts[kind] = counts.get(kind, 0) + 1
            entry = {"t_rel_s": round(r.get("t", t0) - t0, 3),
                     "event": kind}
            for k in ("step", "path", "ok", "duration_s", "bytes",
                      "restored_step", "consecutive_bad", "bucket",
                      "elapsed_s", "error",
                      # opt_tail (fused optimizer pass) fields: shape
                      # of the pass + its self-timed ms / achieved
                      # GB/s when measured standalone
                      "fused", "buffers", "buffer_bytes",
                      "moment_dtype", "unscale_folded", "self_ms",
                      "gbs",
                      # serving span / request / prefix-cache fields
                      "span", "steps", "slots", "tokens", "dur_s",
                      "weight_dtype", "weight_bytes", "tp",
                      "uid", "slot", "reason", "new_tokens",
                      "ttft_s", "chunk", "start", "matched_tokens",
                      "shared_pages", "tokens_skipped", "copied",
                      # fleet router / failover / trace fields
                      "replica", "slo", "affinity", "replays",
                      "migrated", "itl_ms", "rejected", "lost",
                      # fault-tolerance tier fields: quarantine /
                      # deadline / hedge / brownout / journal events
                      "cause", "retry", "consecutive", "hedged",
                      "primary", "from_level", "to_level",
                      "free_page_frac", "queue_depth", "resumed",
                      "corrupt", "gapped",
                      # disaggregation / offload-tier fields: page
                      # movement routes, sizes, and fault-in misses
                      "src", "dst", "pages", "misses"):
                if k in r:
                    entry[k] = r[k]
            timeline.append(entry)
        out["events"] = {"counts": counts, "timeline": timeline}

    serving = summarize_serving(records)
    if serving:
        out["serving"] = serving

    fleet = summarize_fleet(records)
    if fleet:
        out["fleet"] = fleet

    flt = summarize_faults(records)
    if flt:
        out["faults"] = flt

    kvm = summarize_kv_movement(records)
    if kvm:
        out["kv_movement"] = kvm

    return out


def compare_to_bench(summary: Dict[str, Any], bench_path: str
                     ) -> Optional[Dict[str, Any]]:
    """Ratio of this run's headline tokens/s to a BENCH_*.json record's
    (``{"metric": ..., "value": ..., "unit": "tokens/s"}``)."""
    try:
        with open(bench_path) as f:
            bench = json.load(f)
    except (OSError, ValueError) as e:
        print(f"cannot read bench record {bench_path}: {e}",
              file=sys.stderr)
        return None
    bval = bench.get("value")
    if not bval or "value" not in summary:
        return None
    return {
        "bench_metric": bench.get("metric"),
        "bench_value": bval,
        "run_value": summary["value"],
        "run_vs_bench": round(summary["value"] / bval, 3),
    }


def format_report(summary: Dict[str, Any]) -> str:
    lines = []
    runs = ", ".join(summary.get("runs") or ["?"])
    lines.append(f"== metrics report: {runs} "
                 f"({summary.get('n_records', 0)} records) ==")
    st = summary.get("steps")
    if st:
        lines.append(f"steps {st['first']}..{st['last']} "
                     f"({st['count']} logged)")
    for k, s in (summary.get("scalars") or {}).items():
        lines.append(f"  {k}: first {s['first']:.4f}  last {s['last']:.4f}"
                     f"  min {s['min']:.4f}  max {s['max']:.4f}")
    thr = summary.get("throughput")
    if thr:
        lines.append("throughput trajectory (per flush window):")
        for w in thr["windows"]:
            row = f"  step {w.get('step')}: "
            if "ms_per_step" in w:
                row += f"{w['ms_per_step']:.2f} ms/step"
            if "tokens_per_sec" in w:
                row += f"  {w['tokens_per_sec']:,.0f} tokens/s"
            if "mfu" in w:
                row += f"  mfu {w['mfu']:.4f}"
            lines.append(row)
        for key in ("tokens_per_sec", "ms_per_step", "mfu"):
            if key in thr:
                s = thr[key]
                lines.append(
                    f"  {key}: mean {s['mean']:.4g}  best {s['best']:.4g}"
                    f"  final {s['final']:.4g}")
    met = summary.get("meters")
    if met:
        if "host_phase_ms_per_step" in met:
            lines.append("host phase time (ms/step): " + "  ".join(
                f"{k} {v:.3f}" for k, v in
                met["host_phase_ms_per_step"].items()))
        if "counters" in met:
            lines.append("counters: " + "  ".join(
                f"{k}={v}" for k, v in met["counters"].items()))
    sv = summary.get("serving")
    if sv:
        lines.append("serving summary:")
        if "decode_tokens_per_sec" in sv:
            s = sv["decode_tokens_per_sec"]
            lines.append(
                f"  decode tokens/s per window: mean {s['mean']:.4g}  "
                f"best {s['best']:.4g}  final {s['final']:.4g}")
        if "weight_stream_gbs" in sv or "weight_dtype" in sv:
            g = sv.get("weight_stream_gbs")
            row = "  weight stream: "
            if "weight_dtype" in sv:
                wd = sv["weight_dtype"]
                row += (wd if isinstance(wd, str) else "/".join(wd))
                row += " weights"
            if "tp" in sv:
                t = sv["tp"]
                row += (f", tp={t}" if isinstance(t, int)
                        else ", tp=" + "/".join(str(x) for x in t))
            if g:
                row += (f", mean {g['mean']:.4g} GB/s/chip  "
                        f"best {g['best']:.4g} GB/s/chip")
            lines.append(row)
        if "inter_token_latency_ms" in sv:
            i = sv["inter_token_latency_ms"]
            lines.append(
                f"  inter-token latency (window means): "
                f"p50 {i['p50']} ms  p90 {i['p90']} ms  "
                f"p99 {i['p99']} ms")
        if "ttft_s" in sv:
            t = sv["ttft_s"]
            # honesty note: "exact" TTFTs are admitted-event-to-
            # prefill-span wall time — no harvest quantization — but
            # under chunked prefill ADMISSION still progressed one
            # chunk per serving step, so TTFT includes the interleaved
            # decode steps (that interleaving is the point — decode
            # never stalled for a whole prompt); "completion"-sourced
            # TTFTs (old streams) stay harvest-quantized
            if t.get("source") == "exact":
                granularity = ("exact admit-to-first-token spans"
                               + (", chunk-granularity admission"
                                  if "prefill_chunks" in sv else ""))
            else:
                granularity = ("quantized to the harvest cadence"
                               + (", chunk-granularity admission"
                                  if "prefill_chunks" in sv else ""))
            lines.append(
                f"  time-to-first-token: p50 {t['p50']}s  "
                f"p95 {t['p95']}s  max {t['max']}s "
                f"({granularity})")
        if "requests" in sv:
            r = sv["requests"]
            by = "  ".join(f"{k}={v}"
                           for k, v in sorted(r["by_reason"].items()))
            lines.append(f"  requests completed: {r['completed']} ({by})")
        if "prefill_spans" in sv:
            lines.append(
                f"  prefill: {sv['prefill_spans']} admissions, "
                f"{sv.get('prefill_tokens', '?')} prompt tokens")
        if "prefill_chunks" in sv:
            pc = sv["prefill_chunks"]
            row = (f"  prefill chunks: {pc['count']} "
                   f"({pc['tokens']} tokens")
            if "mean_ms" in pc:
                row += (f", mean {pc['mean_ms']} ms, "
                        f"max {pc['max_ms']} ms")
            lines.append(row + ")")
        if "prefix_cache" in sv:
            px = sv["prefix_cache"]
            lines.append(
                f"  prefix cache: {px['hits']}/{px['admissions']} "
                f"admissions hit ({px['hit_rate']:.0%}), "
                f"{px['pages_shared']} pages shared, "
                f"{px['prefill_tokens_skipped']} prefill tokens "
                f"skipped, {px['pages_copied']} CoW copies")
        if "speculation" in sv:
            sp = sv["speculation"]
            row = (f"  speculation: {sp['committed']} tokens in "
                   f"{sp['verify_steps']} verify steps")
            if sp.get("committed_per_slot_step") is not None:
                row += (f" ({sp['committed_per_slot_step']:.2f} "
                        "tokens/slot-step)")
            if sp.get("wasted_verify_fraction") is not None:
                row += (f", wasted-verify "
                        f"{sp['wasted_verify_fraction']:.0%}")
            if sp.get("offramp_commits"):
                row += f", {sp['offramp_commits']} off-ramp commits"
            lines.append(row)
            if sp.get("draft_wall_fraction") is not None:
                lines.append(
                    f"    draft model cost: {sp['draft_wall_s']:.3f} s "
                    f"({sp['draft_wall_fraction']:.0%} of decode wall)")
            if sp.get("accepted_per_step_hist"):
                hist = "  ".join(
                    f"{k}:{v}" for k, v in sorted(
                        sp["accepted_per_step_hist"].items(),
                        key=lambda kv: int(kv[0])))
                lines.append(
                    f"    committed-per-step histogram: {hist}")
            for src, tot in sorted(
                    (sp.get("by_source") or {}).items()):
                row = (f"    [{src}] drafted {tot['drafted']}  "
                       f"accepted {tot['accepted']}")
                if "hit_rate" in tot:
                    row += f"  hit rate {tot['hit_rate']:.0%}"
                lines.append(row)
    fl = summary.get("fleet")
    if fl:
        lines.append("fleet summary:")
        if "routed" in fl:
            routed = "  ".join(f"{k}={v}"
                               for k, v in sorted(fl["routed"].items()))
            lines.append(
                f"  routed: {routed} "
                f"(affinity hits {fl.get('affinity_routed', 0)})")
        ledger = "  ".join(
            f"{k}={fl[k]}" for k in ("rejected", "migrated",
                                     "replicas_dead") if k in fl)
        if ledger:
            lines.append(f"  ledger: {ledger}")
        tr = fl.get("trace")
        if tr:
            lines.append(
                f"  trace: {tr['completed']}/{tr['requests']} "
                f"completed, {tr['lost']} lost")
        for name, c in (fl.get("by_class") or {}).items():
            row = f"  [{name}] n={c['n']}"
            if "ttft_s" in c:
                row += (f"  ttft p50 {c['ttft_s']['p50']}s "
                        f"p99 {c['ttft_s']['p99']}s")
            if "itl_ms" in c:
                row += (f"  itl p50 {c['itl_ms']['p50']}ms "
                        f"p99 {c['itl_ms']['p99']}ms")
            lines.append(row)
    ft = summary.get("faults")
    if ft:
        lines.append("fault / recovery summary:")
        rf = ft.get("replica_faults")
        if rf:
            by = "  ".join(f"{k}={v}"
                           for k, v in sorted(rf["by_replica"].items()))
            lines.append(f"  replica faults: {rf['count']} ({by})")
        if "quarantined" in ft:
            q = "  ".join(f"{r['replica']}({r['cause']})"
                          for r in ft["quarantined"])
            lines.append(f"  quarantined: {q}")
        mg = ft.get("migrations")
        if mg:
            by = "  ".join(f"{k}={v}"
                           for k, v in sorted(mg["by_cause"].items()))
            lines.append(f"  migrations: {mg['count']} ({by})")
        dm = ft.get("deadline_misses")
        if dm:
            lines.append(
                f"  deadline misses: {dm['count']} "
                f"({dm['retried']} retried, {dm['terminal']} terminal)")
        hg = ft.get("hedging")
        if hg:
            lines.append(
                f"  hedging: {hg['spawned']} spawned, "
                f"{hg['wins']} wins, {hg['losses']} losses")
        br = ft.get("brownout")
        if br:
            lines.append(
                f"  brownout: {br['transitions']} transitions "
                f"(peak level {br['max_level']})")
        for jr in ft.get("journal_replays", []):
            lines.append(
                f"  journal replay: {jr.get('resumed', 0)} resumed, "
                f"{jr.get('completed', 0)} already complete, "
                f"{jr.get('corrupt', 0)} corrupt, "
                f"{jr.get('gapped', 0)} gapped")
        for name, a in sorted((ft.get("slo_attainment") or {}).items()):
            lines.append(
                f"  [{name}] slo attainment {a['attainment']:.1%} "
                f"({a['deadline_missed']}/{a['n']} deadline-missed)")
    kvm = summary.get("kv_movement")
    if kvm:
        lines.append("kv movement summary:")
        ho = kvm.get("handoffs")
        if ho:
            routes = "  ".join(f"{k}x{v}"
                               for k, v in sorted(ho["by_route"].items()))
            row = (f"  handoffs: {ho['count']} ({ho['pages']} pages, "
                   f"{ho['wire_bytes']:,} wire bytes; {routes})")
            if "ms" in ho:
                row += (f"  mean {ho['ms']['mean']} ms  "
                        f"max {ho['ms']['max']} ms")
            lines.append(row)
        of = kvm.get("offload")
        if of:
            lines.append(
                f"  offloaded: {of['pages']} pages in {of['events']} "
                f"evictions ({of['wire_bytes']:,} bytes to host)")
        fi = kvm.get("faultin")
        if fi:
            row = (f"  fault-in: {fi['pages']} pages in {fi['events']} "
                   f"walks ({fi['wire_bytes']:,} bytes back), "
                   f"hit rate {fi['hit_rate']:.0%}, "
                   f"{fi['prefill_tokens_saved']} prefill tokens saved")
            if "ms" in fi:
                row += (f"  mean {fi['ms']['mean']} ms  "
                        f"max {fi['ms']['max']} ms")
            lines.append(row)
    ev = summary.get("events")
    if ev:
        lines.append("events: " + "  ".join(
            f"{k}x{v}" for k, v in sorted(ev["counts"].items())))
        for e in ev["timeline"]:
            extra = "  ".join(
                f"{k}={e[k]}" for k in e if k not in ("t_rel_s", "event"))
            lines.append(f"  +{e['t_rel_s']:9.3f}s  {e['event']}  {extra}")
    cmp_ = summary.get("vs_bench")
    if cmp_:
        lines.append(
            f"vs bench {cmp_['bench_metric']}: run {cmp_['run_value']:,} "
            f"/ bench {cmp_['bench_value']:,} = {cmp_['run_vs_bench']}x")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("jsonl", help="metrics JSONL file (MetricsLogger "
                                  "output)")
    ap.add_argument("--json", default=None,
                    help="also write the summary dict here")
    ap.add_argument("--bench", default=None,
                    help="a BENCH_*.json record to compare the "
                         "headline tokens/s against")
    args = ap.parse_args(argv)
    records = load_records(args.jsonl)
    if not records:
        print(f"{args.jsonl}: no records", file=sys.stderr)
        return 1
    summary = summarize(records)
    if args.bench:
        cmp_ = compare_to_bench(summary, args.bench)
        if cmp_:
            summary["vs_bench"] = cmp_
    print(format_report(summary))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"wrote {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
