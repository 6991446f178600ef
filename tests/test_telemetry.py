"""Runtime telemetry subsystem tests: async scalar harvesting (the
dispatch-spy proof that the default flush cadence performs ZERO
per-step blocking device→host transfers in a GPT training loop),
MetricsLogger sinks/meters, StepStats rates, the event bus and its
subsystem wiring (guard / watchdog / checkpoint / autoresume /
Reducer comm buckets), TraceTrigger, log_util validation, and
tools/metrics_report."""

import json
import logging
import math
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.telemetry import events
from apex_tpu.telemetry import metrics as metrics_mod
from apex_tpu.telemetry.events import ring_wire_bytes
from apex_tpu.telemetry.metrics import (
    MetricsLogger,
    StepStats,
    device_peak_flops,
    transformer_flops_per_token,
)
from apex_tpu.telemetry.spans import PHASES, TraceTrigger, phase


class CapturingSink:
    def __init__(self):
        self.evs = []

    def event(self, kind, **fields):
        self.evs.append((kind, fields))

    def kinds(self):
        return [k for k, _ in self.evs]

    def of(self, kind):
        return [f for k, f in self.evs if k == kind]


@pytest.fixture
def sink():
    cap = CapturingSink()
    events.add_sink(cap)
    try:
        yield cap
    finally:
        events.remove_sink(cap)


def read_jsonl(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# --------------------------------------------------------------- event bus
class TestEventBus:
    def test_emit_without_sinks_is_noop(self):
        events.emit("whatever", x=1)  # must not raise

    def test_sink_receives_and_scoped_removal(self):
        cap = CapturingSink()
        with events.sink(cap):
            events.emit("a", x=1)
        events.emit("b", x=2)  # after removal
        assert cap.kinds() == ["a"]

    def test_broken_sink_never_breaks_emit(self, sink):
        class Broken:
            def event(self, kind, **f):
                raise RuntimeError("boom")

        with events.sink(Broken()):
            events.emit("a")  # must not raise
        assert sink.kinds() == ["a"]  # healthy sink still got it

    def test_non_sink_rejected(self):
        with pytest.raises(TypeError):
            events.add_sink(object())

    def test_double_add_single_delivery(self, sink):
        events.add_sink(sink)  # second add is a no-op
        events.emit("once")
        assert sink.kinds() == ["once"]

    def test_ring_wire_bytes_model(self):
        # the comm_audit docstring formulas, byte for byte
        assert ring_wire_bytes("all-reduce", 4, 100) == 150.0
        assert ring_wire_bytes("reduce-scatter", 4, 100) == 75.0
        assert ring_wire_bytes("all-to-all", 4, 100) == 75.0
        assert ring_wire_bytes("all-gather", 4, 0, result_bytes=100) == 75.0
        assert ring_wire_bytes("collective-permute", 4, 100) == 100.0
        assert ring_wire_bytes("all-reduce", 1, 100) == 0.0

    def test_ring_model_matches_comm_audit(self):
        import importlib.util

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "comm_audit", os.path.join(root, "tools", "comm_audit.py"))
        ca = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ca)
        rec = {"op": "all-reduce", "operand_bytes": 1024,
               "result_bytes": 1024,
               "replica_groups": [[0, 1], [2, 3]]}
        assert ca._wire_bytes(rec) == ring_wire_bytes(
            "all-reduce", 2, 1024, result_bytes=1024)


# ----------------------------------------------------------- MetricsLogger
class TestMetricsLogger:
    def test_jsonl_step_records_and_cadence(self, tmp_path):
        p = str(tmp_path / "m.jsonl")
        tlm = MetricsLogger(jsonl_path=p, console=False, flush_every=4)
        for i in range(10):
            tlm.log_scalars(i, loss=float(i))
        # two full cadence windows flushed, 2 records pending
        recs = read_jsonl(p)
        assert len([r for r in recs if r["kind"] == "step"]) == 8
        tlm.close()  # drains the rest
        recs = read_jsonl(p)
        steps = [r for r in recs if r["kind"] == "step"]
        assert [r["step"] for r in steps] == list(range(10))
        assert steps[-1]["loss"] == 9.0
        assert tlm.last == {"loss": 9.0}
        assert tlm.last_step == 9

    def test_device_scalars_resolve_batched(self, tmp_path, monkeypatch):
        calls = []
        real = metrics_mod._device_get
        monkeypatch.setattr(metrics_mod, "_device_get",
                            lambda h: (calls.append(len(h)), real(h))[1])
        tlm = MetricsLogger(jsonl_path=str(tmp_path / "m.jsonl"),
                            console=False, flush_every=5)
        for i in range(10):
            tlm.log_scalars(i, loss=jnp.float32(i), lr=jnp.float32(0.1))
        tlm.close()
        # ONE device_get per flush window, each carrying the whole
        # window's scalars (5 steps x 2 scalars)
        assert calls == [10, 10]
        assert tlm.n_resolves == 2

    def test_flush_every_one_is_synchronous(self, tmp_path):
        tlm = MetricsLogger(jsonl_path=str(tmp_path / "m.jsonl"),
                            console=False, flush_every=1)
        tlm.log_scalars(0, loss=jnp.float32(1.5))
        assert tlm.last == {"loss": 1.5}  # resolved immediately

    def test_meters_counters_gauges_timings(self, tmp_path):
        p = str(tmp_path / "m.jsonl")
        tlm = MetricsLogger(jsonl_path=p, console=False, flush_every=100)
        tlm.counter("saves")
        tlm.counter("saves", 2)
        tlm.gauge("scale", 128.0)
        tlm.gauge("gnorm", jnp.float32(0.5))  # device gauge
        with tlm.timing("data"):
            pass
        tlm.log_scalars(0, loss=1.0)
        tlm.close()
        meters = [r for r in read_jsonl(p) if r["kind"] == "meters"]
        assert len(meters) == 1
        assert meters[0]["counters"] == {"saves": 3}
        assert meters[0]["gauges"]["scale"] == 128.0
        assert meters[0]["gauges"]["gnorm"] == 0.5
        assert meters[0]["timings_ms"]["data"] >= 0

    def test_event_written_immediately(self, tmp_path):
        p = str(tmp_path / "m.jsonl")
        tlm = MetricsLogger(jsonl_path=p, console=False, flush_every=100)
        tlm.event("checkpoint_save", path="/x", duration_s=0.1)
        recs = read_jsonl(p)  # before any flush
        assert recs[0]["kind"] == "event"
        assert recs[0]["event"] == "checkpoint_save"
        tlm.close()

    def test_attach_events_routes_bus_and_close_deregisters(
            self, tmp_path):
        p = str(tmp_path / "m.jsonl")
        tlm = MetricsLogger(jsonl_path=p, console=False).attach_events()
        try:
            events.emit("guard_warn", step=3)
        finally:
            tlm.close()
        # close() removed the sink: later bus traffic must not land in
        # the dead logger's file (the exception-path leak the trainers
        # rely on close() to prevent)
        events.emit("guard_warn", step=4)
        assert not events.have_sinks()
        recs = read_jsonl(p)
        assert len(recs) == 1
        assert recs[0]["event"] == "guard_warn" and recs[0]["step"] == 3

    def test_console_line(self):
        lines = []
        tlm = MetricsLogger(console=True, flush_every=2,
                            print_fn=lines.append)
        tlm.log_scalars(0, loss=1.25)
        tlm.log_scalars(1, loss=1.5)
        assert lines and "step 1" in lines[0] and "1.5000" in lines[0]
        tlm.close()

    def test_validation(self):
        with pytest.raises(ValueError):
            MetricsLogger(flush_every=0)

    def test_overhead_accounting_excludes_resolve_wait(self, tmp_path):
        tlm = MetricsLogger(jsonl_path=str(tmp_path / "m.jsonl"),
                            console=False, flush_every=2)
        tlm.log_scalars(0, loss=jnp.float32(1.0))
        tlm.log_scalars(1, loss=jnp.float32(2.0))
        tlm.close()
        assert tlm.overhead_s >= 0
        assert tlm.resolve_wait_s >= 0


# --------------------------------------------------------------- StepStats
class TestStepStats:
    def test_rates_with_fake_clock(self):
        t = [0.0]
        stats = StepStats(tokens_per_step=100, flops_per_token=10,
                          peak_flops=1e4, time_fn=lambda: t[0])
        stats.begin()
        t[0] = 1.0
        stats.tick(10)
        iv = stats.interval()
        assert iv["ms_per_step"] == pytest.approx(100.0)
        assert iv["tokens_per_sec"] == pytest.approx(1000.0)
        # mfu = tps * flops_per_token / peak = 1000*10/1e4
        assert iv["mfu"] == pytest.approx(1.0)
        # a second interval with no new ticks is empty
        assert stats.interval() == {}
        t[0] = 2.0
        stats.tick(5)
        iv2 = stats.interval()
        assert iv2["ms_per_step"] == pytest.approx(200.0)
        s = stats.summary()
        assert s["timed_steps"] == 15
        assert s["ms_per_step"] == pytest.approx(2000.0 / 15)

    def test_auto_peak_is_the_whole_jobs(self, monkeypatch):
        # tokens_per_step is global, so the auto peak is per-chip peak
        # x every device: one chip's peak would report a four-chip job
        # at four times its utilization
        monkeypatch.setattr(metrics_mod, "device_peak_flops",
                            lambda device=None: 100.0)
        stats = StepStats(tokens_per_step=100, flops_per_token=10)
        assert stats.peak_flops == 100.0 * jax.device_count()
        # and a lookup that raises (unknown TPU kind) is not swallowed

        def unknown(device=None):
            raise ValueError("no peak-FLOP/s row")

        monkeypatch.setattr(metrics_mod, "device_peak_flops", unknown)
        with pytest.raises(ValueError, match="no peak"):
            StepStats(tokens_per_step=1, flops_per_token=1).peak_flops

    def test_begin_excludes_first_step(self):
        t = [0.0]
        stats = StepStats(tokens_per_step=1, time_fn=lambda: t[0])
        t[0] = 5.0  # "compile" happened before begin
        stats.begin()
        t[0] = 6.0
        stats.tick()
        assert stats.summary()["ms_per_step"] == pytest.approx(1000.0)

    def test_no_ticks_summary(self):
        stats = StepStats()
        assert stats.summary() == {"timed_steps": 0}
        assert stats.interval() == {}

    def test_flop_model_and_peak_table(self):
        # 6N + 12*L*h*s — the bench/scale_mfu numerator
        assert transformer_flops_per_token(1000, 2, 8, 16) == \
            6 * 1000 + 12 * 2 * 8 * 16
        # CPU devices have no peak entry: MFU omitted, not fabricated
        assert device_peak_flops(jax.devices()[0]) is None

        class FakeDev:
            platform = "tpu"
            device_kind = "TPU v5e"

        assert device_peak_flops(FakeDev()) == 197e12

    def test_unknown_tpu_kind_raises(self):
        # a TPU the table does not know must not silently drop MFU
        class FakeDev:
            platform = "tpu"
            device_kind = "TPU v99x"

        with pytest.raises(ValueError, match="v99x"):
            device_peak_flops(FakeDev())


# --------------------------------------- the dispatch-spy GPT-loop proof
class BlockingSpyScalar:
    """Wraps a device scalar; any blocking host conversion outside the
    sanctioned batched resolve is recorded.  ``_run`` widens
    ``metrics._is_device_value`` so MetricsLogger treats it as a device
    value."""

    def __init__(self, arr, counter):
        self._arr = arr
        self._counter = counter

    def __float__(self):
        self._counter["blocking"] += 1
        return float(self._arr)

    def __array__(self, *a, **k):
        self._counter["blocking"] += 1
        return np.asarray(self._arr)

    def __bool__(self):
        self._counter["blocking"] += 1
        return bool(self._arr)


class TestDispatchSpyGPTLoop:
    """The acceptance-criteria test: at the default flush cadence the
    GPT training loop performs ZERO per-step blocking device→host
    transfers — scalars resolve only inside the flush's batched
    device_get, once per cadence window."""

    @pytest.fixture(scope="class")
    def gpt_loop(self):
        from apex_tpu.models import GPTConfig, GPTModel
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.transformer import parallel_state
        from apex_tpu.transformer.tensor_parallel.layers import (
            state_specs_like,
        )
        from jax.sharding import NamedSharding

        mesh = parallel_state.initialize_model_parallel()
        try:
            cfg = GPTConfig(
                vocab_size=64, num_layers=1, hidden_size=32,
                num_attention_heads=4, max_position_embeddings=16,
                compute_dtype=jnp.float32, remat=False,
                attention_impl="xla",
            )
            model = GPTModel(cfg)
            params = model.init(jax.random.PRNGKey(0))
            specs = model.param_specs()
            opt = FusedAdam(lr=1e-3)
            opt_state = opt.init(params)
            opt_specs = state_specs_like(specs, opt_state)

            def train_step(p, s, tokens, targets):
                with phase("fwd_bwd"):
                    loss, grads = jax.value_and_grad(model.loss)(
                        p, tokens, targets)
                with phase("grad_sync"):
                    grads = jax.tree.map(
                        lambda g: jax.lax.pmean(g, "dp"), grads)
                with phase("optimizer"):
                    p, s = opt.step(s, grads, p)
                return p, s, loss

            step = jax.jit(jax.shard_map(
                train_step, mesh=mesh,
                in_specs=(specs, opt_specs, P("dp"), P("dp")),
                out_specs=(specs, opt_specs, P()),
            ))
            place = lambda tree, sp: jax.device_put(
                tree, jax.tree.map(
                    lambda s_: NamedSharding(mesh, s_), sp,
                    is_leaf=lambda x: isinstance(x, P)))
            dp = mesh.shape["dp"]
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (dp, 16), 0, 64)
            targets = jnp.roll(tokens, -1, axis=1)
            yield (place(params, specs), place(opt_state, opt_specs),
                   step, tokens, targets)
        finally:
            parallel_state.destroy_model_parallel()

    def _run(self, gpt_loop, tmp_path, monkeypatch, steps, flush_every):
        p, s, step, tokens, targets = gpt_loop
        counter = {"blocking": 0, "resolves": 0}
        real = metrics_mod._device_get

        def spy_get(handles):
            counter["resolves"] += 1
            return real([h._arr if isinstance(h, BlockingSpyScalar)
                         else h for h in handles])

        monkeypatch.setattr(metrics_mod, "_device_get", spy_get)
        monkeypatch.setattr(
            metrics_mod, "_is_device_value",
            lambda v: isinstance(v, (jax.Array, BlockingSpyScalar)))
        tlm = MetricsLogger(jsonl_path=str(tmp_path / "m.jsonl"),
                            console=False, flush_every=flush_every)
        loss = None
        for i in range(steps):
            p, s, loss = step(p, s, tokens, targets)
            tlm.log_scalars(i, loss=BlockingSpyScalar(loss, counter))
        tlm.close()
        return counter, tlm, loss

    def test_default_cadence_zero_per_step_blocking_transfers(
            self, gpt_loop, tmp_path, monkeypatch):
        STEPS = 20
        counter, tlm, loss = self._run(
            gpt_loop, tmp_path, monkeypatch, STEPS, flush_every=10)
        # the proof: NO wrapped scalar was ever converted outside the
        # batched resolve, and the batched resolve ran once per cadence
        # window — not once per step
        assert counter["blocking"] == 0
        assert counter["resolves"] == math.ceil(STEPS / 10)
        # and the values still landed, exact
        recs = read_jsonl(str(tmp_path / "m.jsonl"))
        steps = [r for r in recs if r["kind"] == "step"]
        assert len(steps) == STEPS
        assert steps[-1]["loss"] == pytest.approx(float(loss))

    def test_cadence_one_reproduces_per_step_sync(
            self, gpt_loop, tmp_path, monkeypatch):
        # control: flush_every=1 is the seed's synchronous behaviour —
        # one resolve per step (the spy DETECTS what cadence removes)
        STEPS = 6
        counter, _, _ = self._run(
            gpt_loop, tmp_path, monkeypatch, STEPS, flush_every=1)
        assert counter["resolves"] == STEPS


# ------------------------------------------------------------ phase spans
class TestPhases:
    def test_phase_names_hlo(self):
        def f(x):
            with phase("fwd_bwd"):
                return jnp.sin(x) + 1

        lowered = jax.jit(f).lower(jnp.ones(4))
        text = lowered.as_text(debug_info=True)
        assert "tlm.fwd_bwd" in text

    def test_phases_nest_and_cost_nothing_outside_jit(self):
        with phase("data"), phase("checkpoint"):
            pass
        assert "grad_sync" in PHASES


# ----------------------------------------------------------- TraceTrigger
class TestTraceTrigger:
    def test_touch_file_capture_and_rearm(self, tmp_path):
        tdir = str(tmp_path / "traces")
        trig = TraceTrigger(trace_dir=tdir, steps=2, poll_every=1)
        f = jax.jit(lambda x: x * 2)
        assert not trig.poll(0)  # nothing armed
        open(trig.trigger_file, "w").close()  # arm
        assert trig.poll(1)  # capture opens
        assert not os.path.exists(trig.trigger_file)  # consumed
        jax.block_until_ready(f(jnp.ones(8)))
        assert trig.poll(2)  # window step 1
        jax.block_until_ready(f(jnp.ones(8)))
        assert not trig.poll(3)  # window closed
        assert trig.captures == 1
        out = os.path.join(tdir, "step1")
        assert os.path.isdir(out) and os.listdir(out)
        # re-touch re-arms a second capture
        open(trig.trigger_file, "w").close()
        assert trig.poll(4)
        trig.close()
        assert trig.captures == 2

    def test_touch_file_dir_override(self, tmp_path):
        tdir = str(tmp_path / "traces")
        other = str(tmp_path / "elsewhere")
        trig = TraceTrigger(trace_dir=tdir, steps=1, poll_every=1)
        with open(trig.trigger_file, "w") as f:
            f.write(other + "\n")
        assert trig.poll(7)
        trig.close()
        assert os.path.isdir(os.path.join(other, "step7"))

    def test_env_arming_one_shot(self, tmp_path, monkeypatch, sink):
        monkeypatch.setenv("APEX_TPU_TRACE_DIR",
                           str(tmp_path / "envtrace"))
        trig = TraceTrigger(steps=1)
        assert trig.poll(0)  # armed by env at startup
        assert not trig.poll(1)
        assert not trig.poll(2)  # one-shot: no re-arm
        assert trig.captures == 1
        assert "trace_start" in [k for k, _ in sink.evs]
        assert "trace_captured" in [k for k, _ in sink.evs]

    def test_validation(self):
        with pytest.raises(ValueError):
            TraceTrigger(poll_every=0)
        with pytest.raises(ValueError):
            TraceTrigger(steps=0)


# -------------------------------------------------------- subsystem wiring
class TestSubsystemEvents:
    def test_guard_warn_and_diverged_events(self, sink):
        from apex_tpu.resilience import DivergenceError, StepGuard

        g = StepGuard(warn_after=1, rollback_after=2, raise_after=2)
        g.observe(False, step=5)
        assert sink.of("guard_warn")[0]["step"] == 5
        with pytest.raises(DivergenceError):
            g.observe(False, step=6)
        assert sink.of("guard_diverged")[0]["consecutive_bad"] == 2

    def test_guard_rollback_event(self, sink, tmp_path):
        from apex_tpu.resilience import StepGuard
        from apex_tpu.utils.autoresume import AutoResume

        ar = AutoResume(str(tmp_path), interval_steps=1)
        ar.maybe_save(1, {"x": np.float32(1.0)})
        g = StepGuard(autoresume=ar, warn_after=1, rollback_after=2,
                      raise_after=5)
        g.observe(False, step=10)
        v = g.observe(False, step=11)
        assert v.action == "rollback"
        ev = sink.of("guard_rollback")[0]
        assert ev["restored_step"] == 1 and ev["restored"] is True

    def test_checkpoint_save_verify_restore_events(self, sink, tmp_path):
        from apex_tpu import checkpoint

        path = str(tmp_path / "ck")
        checkpoint.save(path, {"w": jnp.arange(8.0)})
        ev = sink.of("checkpoint_save")[0]
        assert ev["path"] == path and ev["bytes"] == 32
        assert ev["duration_s"] >= 0
        assert checkpoint.verify(path) == []
        ev = sink.of("checkpoint_verify")[0]
        assert ev["ok"] is True and ev["bad_files"] == []
        checkpoint.restore(path, verify_integrity=True)
        ev = sink.of("checkpoint_restore")[0]
        assert ev["verified"] is True

    def test_checkpoint_corrupt_fallback_event(self, sink, tmp_path):
        from apex_tpu import checkpoint

        good = {"w": np.arange(4, dtype=np.float32)}
        checkpoint.save_step(str(tmp_path), 1, good)
        checkpoint.save_step(str(tmp_path), 2, good)
        blob = os.path.join(str(tmp_path), "step_2", "data.bin")
        with open(blob, "r+b") as f:
            f.write(b"\xff" * 4)  # corrupt the newer step
        tree, step = checkpoint.restore_latest_valid(str(tmp_path))
        assert step == 1
        ev = sink.of("checkpoint_corrupt_fallback")[0]
        assert ev["step"] == 2

    def test_autoresume_gc_and_resume_events(self, sink, tmp_path):
        from apex_tpu.utils.autoresume import AutoResume

        ar = AutoResume(str(tmp_path), interval_steps=1, keep=1)
        ar.maybe_save(1, {"x": np.float32(1.0)})
        ar.maybe_save(2, {"x": np.float32(2.0)})  # GCs step 1
        assert sink.of("autoresume_gc")[0]["step"] == 1
        _, step = ar.resume()
        assert step == 2
        assert sink.of("autoresume_resume")[0]["step"] == 2

    def test_watchdog_heartbeat_file_and_stall_event(
            self, sink, tmp_path):
        import io
        import time as _time

        from apex_tpu.resilience import Watchdog, read_heartbeat

        hb = str(tmp_path / "hb.json")
        wd = Watchdog(deadline_s=0.1, poll_s=0.02, heartbeat_file=hb,
                      stream=io.StringIO())
        with wd:
            wd.beat(step=7)
            rec = read_heartbeat(hb)
            assert rec is not None
            assert rec["step"] == 7 and rec["age_s"] >= 0
            assert rec["pid"] == os.getpid()
            deadline = _time.monotonic() + 5.0
            while wd.stall_count == 0 and _time.monotonic() < deadline:
                _time.sleep(0.02)
        assert wd.stall_count >= 1
        ev = sink.of("watchdog_stall")[0]
        assert ev["deadline_s"] == 0.1 and ev["will_abort"] is False

    def test_read_heartbeat_absent(self, tmp_path):
        from apex_tpu.resilience import read_heartbeat

        assert read_heartbeat(str(tmp_path / "nope.json")) is None
        assert read_heartbeat(None) is None  # no env configured

    def test_reducer_comm_bucket_events_int8(self, sink):
        from apex_tpu.ops.quantization import CompressionConfig
        from apex_tpu.parallel import hierarchical_data_parallel_mesh
        from apex_tpu.parallel.distributed import Reducer
        from apex_tpu.transformer import parallel_state

        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = hierarchical_data_parallel_mesh(ici_size=4)
        red = Reducer(axis_name=("dcn", "ici"), overlap_grad_sync=True,
                      bucket_bytes=256,
                      compression=CompressionConfig(block_size=64))

        def step(xs):
            acc = red.init(xs)
            acc = red.accumulate(acc, xs)
            g, _ = red.reduce(acc)
            return g

        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P(("dcn", "ici")),),
                          out_specs=P(("dcn", "ici"))))(x)
        evs = sink.of("comm_bucket")
        assert evs, "Reducer emitted no comm_bucket events"
        ev = evs[0]
        assert ev["where"] == "reducer"
        assert ev["dcn_size"] == 2 and ev["ici_size"] == 4
        assert ev["compression"] == "int8"
        # per-device leaf is (1,128): 128 fp32 elements = 512B in ONE
        # bucket (buckets group whole leaves; an oversized leaf gets
        # its own bucket rather than being split)
        assert ev["elements"] == 128 and ev["bytes"] == 512
        # RS/AG legs ride ici full-width over the padded buffer; the
        # dcn AR leg is quantized: 128/4=32-elem chunk padded to block
        # 64 -> 64 int8 values + one fp32 scale
        assert ev["rs_ici_wire_bytes"] == round(
            ring_wire_bytes("reduce-scatter", 4, 512))
        assert ev["ag_ici_wire_bytes"] == round(
            ring_wire_bytes("all-gather", 4, 512, result_bytes=512))
        assert ev["ar_dcn_wire_bytes"] == round(
            ring_wire_bytes("all-reduce", 2, 64 + 4))

    def test_ddp_bucketed_comm_events_and_silence_without_sink(self):
        from apex_tpu.parallel import hierarchical_data_parallel_mesh
        from apex_tpu.parallel.distributed import all_reduce_gradients
        from apex_tpu.transformer import parallel_state

        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = hierarchical_data_parallel_mesh(ici_size=4)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32))

        def reduce(g):
            return all_reduce_gradients(g, ("dcn", "ici"),
                                        overlap_grad_sync=True,
                                        bucket_bytes=4096)

        # no sink: traces fine, emits nothing, result correct
        out = jax.jit(jax.shard_map(
            reduce, mesh=mesh, in_specs=(P(("dcn", "ici")),),
            out_specs=P(("dcn", "ici"))))(x)
        ref = np.broadcast_to(np.mean(np.asarray(x), 0, keepdims=True),
                              x.shape)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6,
                                   atol=1e-6)
        cap = CapturingSink()
        with events.sink(cap):
            jax.jit(jax.shard_map(
                lambda g: all_reduce_gradients(
                    g, ("dcn", "ici"), overlap_grad_sync=True,
                    bucket_bytes=64),
                mesh=mesh, in_specs=(P(("dcn", "ici")),),
                out_specs=P(("dcn", "ici"))))(x)
        evs = cap.of("comm_bucket")
        assert evs and evs[0]["where"] == "all_reduce_gradients"
        assert evs[0]["compression"] == "none"


# ----------------------------------------------------- log_util satellite
class TestLogUtil:
    def test_null_handler_installed(self):
        from apex_tpu.transformer.log_util import get_transformer_logger

        get_transformer_logger("somemodule.py")
        root = logging.getLogger("apex_tpu.transformer")
        assert any(isinstance(h, logging.NullHandler)
                   for h in root.handlers)

    def test_set_logging_level_accepts_int_and_str(self):
        from apex_tpu.transformer.log_util import set_logging_level

        root = logging.getLogger("apex_tpu.transformer")
        old = root.level
        try:
            set_logging_level(logging.DEBUG)
            assert root.level == logging.DEBUG
            set_logging_level("warning")
            assert root.level == logging.WARNING
        finally:
            root.setLevel(old)

    @pytest.mark.parametrize("bad", [object(), 1.5, [], None, True,
                                     "VERBOSE"])
    def test_set_logging_level_rejects_garbage(self, bad):
        from apex_tpu.transformer.log_util import set_logging_level

        with pytest.raises((TypeError, ValueError)):
            set_logging_level(bad)


# ------------------------------------------------------ tools/metrics_report
class TestMetricsReport:
    def _write(self, tmp_path, records, junk=False):
        p = str(tmp_path / "run.jsonl")
        with open(p, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
            if junk:
                f.write('{"torn": \n')
        return p

    def _records(self):
        recs = []
        for i in range(6):
            recs.append({"t": 100.0 + i, "kind": "step", "step": i,
                         "run": "test", "loss": 5.0 - i})
        recs.append({"t": 103.0, "kind": "throughput", "step": 2,
                     "ms_per_step": 10.0, "tokens_per_sec": 1000.0,
                     "mfu": 0.4})
        recs.append({"t": 106.0, "kind": "throughput", "step": 5,
                     "ms_per_step": 8.0, "tokens_per_sec": 1250.0,
                     "mfu": 0.5})
        recs.append({"t": 104.0, "kind": "event",
                     "event": "checkpoint_save", "path": "/x",
                     "duration_s": 0.2})
        recs.append({"t": 105.0, "kind": "event", "event": "guard_warn",
                     "step": 4})
        recs.append({"t": 106.5, "kind": "meters", "step": 5,
                     "counters": {"saves": 1},
                     "timings_ms": {"data": 6.0}})
        return recs

    def test_summarize(self, tmp_path):
        from tools.metrics_report import load_records, summarize

        recs = load_records(self._write(tmp_path, self._records(),
                                        junk=True))
        s = summarize(recs)
        assert s["runs"] == ["test"]
        assert s["steps"]["count"] == 6
        assert s["scalars"]["loss"]["first"] == 5.0
        assert s["scalars"]["loss"]["last"] == 0.0
        assert s["value"] == 1250.0 and s["unit"] == "tokens/s"
        assert s["throughput"]["ms_per_step"]["best"] == 8.0  # min!
        assert s["throughput"]["mfu"]["final"] == 0.5
        assert s["events"]["counts"] == {"checkpoint_save": 1,
                                         "guard_warn": 1}
        assert s["events"]["timeline"][0]["t_rel_s"] == 4.0
        assert s["meters"]["host_phase_ms_per_step"]["data"] == 1.0

    def test_report_and_bench_compare(self, tmp_path, capsys):
        from tools.metrics_report import main

        p = self._write(tmp_path, self._records())
        bench = str(tmp_path / "BENCH.json")
        with open(bench, "w") as f:
            json.dump({"metric": "gpt_tp1_tokens_per_sec",
                       "value": 2500.0, "unit": "tokens/s"}, f)
        outj = str(tmp_path / "summary.json")
        assert main([p, "--bench", bench, "--json", outj]) == 0
        text = capsys.readouterr().out
        assert "throughput trajectory" in text
        assert "guard_warn" in text
        assert "0.5x" in text  # 1250 / 2500
        with open(outj) as f:
            s = json.load(f)
        assert s["vs_bench"]["run_vs_bench"] == 0.5

    def test_empty_file(self, tmp_path):
        from tools.metrics_report import main

        p = str(tmp_path / "empty.jsonl")
        open(p, "w").close()
        assert main([p]) == 1
