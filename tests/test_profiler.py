"""Engine step-timeline profiler tests (server/profiler.py).

The attribution invariant under test: the profiler's three buckets —
dispatch wall, host-sync gap, idle gap — tile the engine thread's
tracked timeline, so their shares sum to 100% and a ROADMAP item-2 lever
(multi-step scheduling, device-side stop) shows up as host-sync share
moving, not as unexplained wall.
"""

import json
import pathlib
from types import SimpleNamespace

import pytest

from llm_instance_gateway_tpu.metrics_registry import ENGINE_PHASES
from llm_instance_gateway_tpu.server.profiler import (
    GAP_HOST,
    GAP_IDLE,
    NO_PHASE,
    PHASE_ON,
    StepProfiler,
    render_profile,
)
from tools import profile_report

REPO = pathlib.Path(__file__).resolve().parent.parent


class TestStepProfiler:
    def test_gap_attribution_host_vs_idle(self):
        p = StepProfiler(capacity=16)
        p.note_dispatch("decode", t0=0.0, wall_s=1.0, active=2,
                        total_slots=4)
        p.note_dispatch("decode", t0=1.5, wall_s=1.0, active=2,
                        total_slots=4)  # 0.5s host gap
        p.note_idle()
        p.note_dispatch("decode", t0=3.0, wall_s=1.0, active=2,
                        total_slots=4)  # 0.5s gap, but it contained a wait
        att = p.attribution()
        assert att["dispatch_seconds"] == pytest.approx(3.0)
        assert att["host_sync_seconds"] == pytest.approx(0.5)
        assert att["idle_seconds"] == pytest.approx(0.5)
        assert sum(att["shares"].values()) == pytest.approx(1.0, abs=1e-6)

    def test_prefill_wall_never_counts_as_host_sync(self):
        """A prefill sits in the gap chain on the profiler's clock like any
        dispatch: its wall is dispatch time, and only the host's time on
        either side of it is host-sync."""
        p = StepProfiler(capacity=16)
        p.note_dispatch("decode", t0=0.0, wall_s=1.0)
        p.note_dispatch("prefill", t0=1.2, wall_s=0.3, active=1)
        p.note_dispatch("decode", t0=2.0, wall_s=1.0)
        att = p.attribution()
        assert att["host_sync_seconds"] == pytest.approx(0.7)
        assert att["dispatch_seconds"] == pytest.approx(2.3)
        assert att["dispatch_seconds_by_phase"]["prefill"] == pytest.approx(
            0.3)
        gaps = [r["gap_s"] for r in p.snapshot()["records"]]
        assert gaps == pytest.approx([0.0, 0.2, 0.5])

    def test_a_prompt_streamed_between_decode_blocks_has_no_gap(self):
        """Its wall began before the decode blocks that ran between its
        chunks: no gap before it, and the chain goes on from its end."""
        p = StepProfiler(capacity=16)
        p.note_dispatch("decode", t0=1.0, wall_s=1.0)
        p.note_dispatch("prefill", t0=0.5, wall_s=1.75, active=1)
        p.note_dispatch("decode", t0=2.5, wall_s=1.0)
        assert [r["gap_s"] for r in p.snapshot()["records"]] == (
            pytest.approx([0.0, 0.0, 0.25]))

    def test_pipelined_overlap_clamps_gap_to_zero(self):
        """A pipelined block's dispatch stamp predates the previous
        block's process end — the gap clamps to zero instead of going
        negative (no host-sync: that is what the pipeline buys)."""
        p = StepProfiler(capacity=16)
        p.note_dispatch("decode", t0=0.0, wall_s=2.0)
        p.note_dispatch("decode", t0=1.0, wall_s=2.0)  # overlapped
        att = p.attribution()
        assert att["host_sync_seconds"] == 0.0
        assert att["idle_seconds"] == 0.0

    def test_ring_is_bounded_but_totals_survive(self):
        p = StepProfiler(capacity=4)
        for i in range(10):
            p.note_dispatch("decode", t0=float(i), wall_s=0.5, active=1,
                            total_slots=2, n_steps=3)
        snap = p.snapshot()
        assert len(snap["records"]) == 4
        assert snap["seq"] == 10
        assert snap["attribution"]["dispatches"] == 10  # counters kept
        assert snap["attribution"]["dispatch_seconds"] == pytest.approx(5.0)

    def test_record_fields_and_slot_churn(self):
        p = StepProfiler(capacity=8)
        p.note_dispatch("decode", t0=0.0, wall_s=0.1, active=2,
                        total_slots=4, n_steps=2)
        p.note_dispatch("decode", t0=0.2, wall_s=0.1, active=3,
                        total_slots=4, n_steps=2)
        r0, r1 = p.snapshot()["records"]
        assert r0["active"] == 2 and r0["slots"] == 4 and r0["n_steps"] == 2
        assert r0["slot_churn"] == 2  # from empty batch
        assert r1["slot_churn"] == 1  # one slot admitted between dispatches
        assert r1["gap_kind"] == GAP_HOST and r1["gap_s"] == pytest.approx(
            0.1)

    def test_exposition_families_render(self):
        p = StepProfiler()
        p.note_dispatch("prefill", t0=-0.3, wall_s=0.2, active=1)
        p.note_dispatch("decode", t0=0.0, wall_s=0.1)
        p.note_idle()
        p.note_dispatch("decode", t0=0.5, wall_s=0.1)
        lines = render_profile(p.hist_state())
        text = "\n".join(lines)
        assert text.count("# TYPE tpu:dispatch_wall_seconds histogram") == 1
        assert text.count("# TYPE tpu:dispatch_gap_seconds histogram") == 1
        assert 'tpu:dispatch_wall_seconds_bucket{phase="decode"' in text
        assert 'tpu:dispatch_wall_seconds_bucket{phase="prefill"' in text
        assert f'tpu:dispatch_gap_seconds_count{{kind="{GAP_IDLE}"}} 1' \
            in text
        # The page parses through the shared contract linter.
        from llm_instance_gateway_tpu.utils import prom_parse

        families = prom_parse.parse_text(text + "\n")
        assert families["tpu:dispatch_wall_seconds_count"]


class FakeClock:
    """An injected clock: ``tick(dt)`` moves it, every read is exact."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def tick(self, dt):
        self.now += dt


class TestPhaseStack:
    def make(self, annotate=None):
        clock = FakeClock()
        return StepProfiler(capacity=16, clock=clock, annotate=annotate), clock

    def test_phases_tile_the_wall_and_a_child_takes_its_time_out(self):
        p, clock = self.make()
        t_start = clock.now
        with p.phase("admit"):
            clock.tick(0.25)
            with p.phase("prefill.stage"):
                clock.tick(0.5)
            clock.tick(0.125)
            with p.phase("prefill.wait"):
                clock.tick(2.0)
        clock.tick(0.0625)  # between phases: the bottom of the stack
        with p.phase("decode.plan") as ph:
            clock.tick(0.03125)
            ph.to("decode.stage")
            clock.tick(1.0)
            ph.to("decode.wait")
            clock.tick(4.0)
        sec = p.phase_seconds()
        assert sec["admit"] == pytest.approx(0.375, abs=1e-12)  # self time
        assert sec["prefill.stage"] == pytest.approx(0.5, abs=1e-12)
        assert sec["prefill.wait"] == pytest.approx(2.0, abs=1e-12)
        assert sec["other"] == pytest.approx(0.0625, abs=1e-12)
        assert sec["decode.plan"] == pytest.approx(0.03125, abs=1e-12)
        assert sec["decode.stage"] == pytest.approx(1.0, abs=1e-12)
        assert sec["decode.wait"] == pytest.approx(4.0, abs=1e-12)
        assert sum(sec.values()) == pytest.approx(clock.now - t_start,
                                                  abs=1e-9)

    def test_open_stretch_is_counted_at_a_scrape(self):
        """A scrape in the middle of a long wait sees the wait so far, so
        the counters tile the wall at any instant, not only at a
        transition."""
        p, clock = self.make()
        t_start = clock.now
        with p.phase("decode.wait"):
            clock.tick(3.0)
            mid = p.phase_seconds()
            assert mid["decode.wait"] == pytest.approx(3.0)
            assert sum(mid.values()) == pytest.approx(clock.now - t_start)
            clock.tick(1.0)
        assert p.phase_seconds()["decode.wait"] == pytest.approx(4.0)

    def test_an_exception_unwinds_the_stack(self):
        p, clock = self.make()
        with pytest.raises(RuntimeError):
            with p.phase("admit"):
                with p.phase("prefill.stage") as ph:
                    ph.to("prefill.wait")
                    clock.tick(1.0)
                    raise RuntimeError("device lost")
        clock.tick(0.5)
        with p.phase("idle"):
            clock.tick(2.0)
        sec = p.phase_seconds()
        assert sec["prefill.wait"] == pytest.approx(1.0)
        assert sec["other"] == pytest.approx(0.5)
        assert sec["idle"] == pytest.approx(2.0)
        assert p._stack == ["other"]

    def test_unknown_phase_is_refused(self):
        p, _ = self.make()
        with pytest.raises(KeyError):
            with p.phase("decode.misc"):
                pass
        with p.phase("decode.plan") as ph:
            with pytest.raises(KeyError):
                ph.to("decode.misc")

    def test_label_set_is_the_registrys(self):
        assert PHASE_ON == dict(ENGINE_PHASES)
        assert len(PHASE_ON) == 12
        assert {n for n, on in ENGINE_PHASES if on == "device"} == {
            "prefill.wait", "decode.wait"}
        p, _ = self.make()
        assert set(p.phase_seconds()) == set(PHASE_ON)

    def test_annotations_nest_like_the_phases(self):
        log = []

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        p, _ = self.make(annotate=Ann)
        with p.phase("admit"):
            with p.phase("prefill.stage") as ph:
                with p.annotation("engine.prefill.enqueue"):
                    pass
                ph.to("prefill.wait")
        assert log == [
            ("enter", "engine.admit"),
            ("enter", "engine.prefill.stage"),
            ("enter", "engine.prefill.enqueue"),
            ("exit", "engine.prefill.enqueue"),
            ("exit", "engine.prefill.stage"),
            ("enter", "engine.prefill.wait"),
            ("exit", "engine.prefill.wait"),
            ("exit", "engine.admit"),
        ]
        # Off JAX (no annotate): a trace-only span is the shared no-op.
        assert self.make()[0].annotation("engine.decode.enqueue") is NO_PHASE

    def test_no_phase_is_a_shared_noop(self):
        with NO_PHASE as ph:
            ph.to("anything at all")
        assert ph is NO_PHASE

    def test_decode_record_carries_the_split_since_the_last_record(self):
        p, clock = self.make()
        for wait in (0.5, 0.25):
            t0 = clock.now
            with p.phase("decode.stage") as ph:
                clock.tick(0.125)
                ph.to("decode.wait")
                clock.tick(wait)
                ph.to("decode.readback")
                clock.tick(0.0625)
                wall = clock.now - t0
                ph.to("decode.emit")
                clock.tick(0.03125)
                ph.to("decode.account")
                p.note_dispatch("decode", t0, wall, active=1, total_slots=2)
        p.note_dispatch("prefill", clock.now, 0.1, active=1)
        r0, r1, r2 = p.snapshot()["records"]
        assert (r0["stage_s"], r0["wait_s"], r0["readback_s"],
                r0["emit_s"]) == (0.125, 0.5, 0.0625, 0.03125)
        assert r1["wait_s"] == 0.25
        for r in (r0, r1):
            assert r["stage_s"] + r["wait_s"] + r["readback_s"] == \
                pytest.approx(r["wall_s"], abs=1e-9)
        assert "stage_s" not in r2  # a prefill record has no decode parts

    def test_attribution_has_the_phase_table(self):
        p, clock = self.make()
        with p.phase("decode.wait"):
            clock.tick(3.0)
        with p.phase("decode.emit"):
            clock.tick(1.0)
        att = p.attribution()
        assert att["thread_seconds"] == pytest.approx(4.0)
        assert att["phases"]["decode.wait"] == {
            "on": "device", "seconds": 3.0, "share": 0.75}
        assert att["phases"]["decode.emit"]["on"] == "host"
        assert sum(r["share"] for r in att["phases"].values()) == \
            pytest.approx(1.0)

    def test_phase_counter_renders_every_series(self):
        p, clock = self.make()
        with p.phase("prefill.wait"):
            clock.tick(0.5)
        text = "\n".join(render_profile(p.hist_state())) + "\n"
        assert text.count(
            "# TYPE tpu:engine_phase_seconds_total counter") == 1
        assert ('tpu:engine_phase_seconds_total{phase="prefill.wait",'
                'on="device"} 0.500000') in text
        from llm_instance_gateway_tpu.utils import prom_parse

        samples = prom_parse.parse_text(text)[
            "tpu:engine_phase_seconds_total"]
        assert {s.labels["phase"] for s in samples} == set(PHASE_ON)
        assert {(s.labels["phase"], s.labels["on"]) for s in samples} == \
            set(ENGINE_PHASES)


@pytest.fixture(scope="module")
def profiled_engine():
    import jax
    import jax.numpy as jnp

    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import TINY_TEST
    from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

    params = transformer.init_params(TINY_TEST, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    engine = Engine(
        TINY_TEST, params,
        EngineConfig(decode_slots=2, max_seq_len=64,
                     prefill_buckets=(8, 16, 32)),
        eos_id=None, dtype=jnp.float32)
    engine.start()
    yield engine, params
    engine.stop()


def run_requests(engine, n=3, max_new=6):
    from llm_instance_gateway_tpu.server.engine import (
        Request,
        SamplingParams,
    )

    for _ in range(n):
        r = engine.generate(
            Request(prompt_tokens=[1, 2, 3], max_new_tokens=max_new,
                    sampling=SamplingParams(temperature=0.0)),
            timeout_s=120)
        assert r.error is None


class TestEngineIntegration:
    def test_engine_charges_profiler_at_dispatch_sites(self, profiled_engine):
        engine, _ = profiled_engine
        run_requests(engine)
        snap = engine.profiler.snapshot()
        phases = set(snap["attribution"]["dispatch_seconds_by_phase"])
        assert {"prefill", "decode"} <= phases
        # Every bucket is tracked and the shares tile the timeline.
        assert snap["attribution"]["tracked_seconds"] > 0
        assert sum(snap["attribution"]["shares"].values()) == pytest.approx(
            1.0, abs=1e-6)
        assert snap["records"], "per-dispatch records recorded"
        occ = [r for r in snap["records"] if r["phase"] == "decode"]
        # (a block whose rows all finished before it was read has no row)
        assert all(0 <= r["active"] <= r["slots"] for r in occ)
        assert sum(r["active"] > 0 for r in occ) >= 3 * 5

    def test_metrics_snapshot_and_exposition(self, profiled_engine):
        engine, _ = profiled_engine
        run_requests(engine, n=1)
        from llm_instance_gateway_tpu.server import metrics as server_metrics
        from llm_instance_gateway_tpu.utils import prom_parse

        snap = engine.metrics_snapshot()
        assert "profile" in snap
        text = server_metrics.render(snap)
        assert "# TYPE tpu:dispatch_wall_seconds histogram" in text
        assert "# TYPE tpu:dispatch_gap_seconds histogram" in text
        assert "# TYPE tpu:engine_phase_seconds_total counter" in text
        samples = prom_parse.parse_text(text)[
            "tpu:engine_phase_seconds_total"]
        by_phase = {s.labels["phase"]: s.value for s in samples}
        assert set(by_phase) == set(PHASE_ON)
        assert by_phase["decode.wait"] > 0 and by_phase["prefill.wait"] > 0

    def test_phases_of_a_run_and_the_dispatch_split(self, profiled_engine):
        """A tiny run leaves the waits and the staging non-zero, and a
        decode record's wait lies inside its wall: the step a block books
        runs from the completion before it (or its own staging, on an idle
        device) to its own, and the thread waits for it after both."""
        engine, _ = profiled_engine
        run_requests(engine)
        snap = engine.profiler.snapshot()
        sec = {n: r["seconds"]
               for n, r in snap["attribution"]["phases"].items()}
        for name in ("decode.wait", "decode.stage", "prefill.wait",
                     "prefill.stage", "prefill.emit", "decode.plan",
                     "decode.readback", "decode.emit", "decode.account",
                     "admit", "idle"):
            assert sec[name] > 0, name
        decode = [r for r in snap["records"] if r["phase"] == "decode"]
        assert decode
        for r in decode:
            assert 0 < r["wait_s"] <= r["wall_s"] + 2e-4, r
            assert r["readback_s"] > 0 and r["emit_s"] > 0

    def test_phases_tile_the_engine_threads_wall(self, profiled_engine):
        """Between two scrapes the phase counters grow by the wall time
        between them: the thread is always in exactly one phase."""
        import time

        engine, _ = profiled_engine
        run_requests(engine, n=1)  # the thread's clock has started
        t0, a = time.perf_counter(), engine.profiler.phase_seconds()
        run_requests(engine, n=2)
        t1, b = time.perf_counter(), engine.profiler.phase_seconds()
        grown = sum(b.values()) - sum(a.values())
        assert grown == pytest.approx(t1 - t0, rel=0.01, abs=2e-3)

    def test_every_phase_the_engine_names_is_registered(self):
        """The label set is closed: a phase name in engine.py that the
        registry lacks would raise on the engine thread."""
        import re

        src = (REPO / "llm_instance_gateway_tpu" / "server"
               / "engine.py").read_text()
        used = set(re.findall(
            r'(?:_phase|_in_phase|ph\.to)\(\s*"([^"]+)"', src))
        assert used, "no phase call found: the pattern is stale"
        assert used <= set(PHASE_ON)
        # every phase but the stack's bottom is entered somewhere
        assert used == set(PHASE_ON) - {"other"}
        enq = set(re.findall(r'_enqueue\("([^"]+)"\)', src))
        assert enq == {"engine.decode.enqueue", "engine.prefill.enqueue"}

    def test_debug_profile_endpoint(self, profiled_engine):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from llm_instance_gateway_tpu.server.api_http import ModelServer

        engine, _ = profiled_engine
        run_requests(engine, n=1)
        server = ModelServer(engine, tokenizer=None, model_name="tiny")

        async def run():
            client = TestClient(TestServer(server.build_app()))
            await client.start_server()
            try:
                resp = await client.get("/debug/profile")
                assert resp.status == 200
                payload = await resp.json()
                assert payload["model"] == "tiny"
                assert "attribution" in payload and "records" in payload
            finally:
                await client.close()

        asyncio.run(run())

    def test_debug_profile_404_when_disabled(self):
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from llm_instance_gateway_tpu.server.api_http import ModelServer

        fake_engine = SimpleNamespace(profiler=None, draining=False,
                                      cfg=SimpleNamespace(role="collocated"))
        server = ModelServer(fake_engine, tokenizer=None, model_name="tiny")

        async def run():
            client = TestClient(TestServer(server.build_app()))
            await client.start_server()
            try:
                resp = await client.get("/debug/profile")
                assert resp.status == 404
            finally:
                await client.close()

        asyncio.run(run())


DECODE_PHASES = {"decode.plan", "decode.stage", "decode.wait",
                 "decode.readback", "decode.emit", "decode.account"}
JIT_NAMES = {
    "_jit_decode": "decode_block", "_jit_prefill": "prefill",
    "_jit_prefill_many": "prefill_many", "_jit_insert": "insert_prefill",
    "_jit_chunk": "prefill_chunk", "_jit_sample_one": "sample_one",
    "_jit_spec_block": "spec_block", "_jit_draft_prefill": "draft_prefill",
    "_jit_draft_insert": "draft_insert",
}


@pytest.fixture(scope="module")
def spec_engine():
    """A speculative engine (it builds every program the engine has)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from llm_instance_gateway_tpu.models import transformer
    from llm_instance_gateway_tpu.models.configs import TINY_TEST
    from llm_instance_gateway_tpu.server.engine import Engine, EngineConfig

    dcfg = dataclasses.replace(
        TINY_TEST, name="tiny-draft", d_model=32, n_layers=1, n_heads=2,
        n_kv_heads=1, d_ff=64, head_dim=16)
    params = transformer.init_params(TINY_TEST, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
    dparams = transformer.init_params(dcfg, jax.random.PRNGKey(7),
                                      dtype=jnp.float32)

    def build(**extra):
        return Engine(
            TINY_TEST, params,
            EngineConfig(decode_slots=2, max_seq_len=64,
                         prefill_buckets=(8, 16), **extra),
            eos_id=None, dtype=jnp.float32,
            **({"draft_params": dparams, "draft_cfg": dcfg}
               if extra.get("speculative_k") else {}))

    return build


class TestSameNamesOnEveryDispatch:
    @pytest.mark.parametrize("layout", [{}, {"paged_kv_block": 8}],
                             ids=["lanes", "paged"])
    @pytest.mark.parametrize("extra", [{}, {"speculative_k": 2}],
                             ids=["plain", "spec"])
    def test_loop_charges_the_decode_and_prefill_phases(self, spec_engine,
                                                        extra, layout):
        engine = spec_engine(**extra, **layout)
        engine.start()
        try:
            run_requests(engine, n=2, max_new=8)
        finally:
            engine.stop()
        sec = engine.profiler.phase_seconds()
        charged = {n for n, v in sec.items() if v > 0}
        assert DECODE_PHASES <= charged, DECODE_PHASES - charged
        assert {"admit", "prefill.stage", "prefill.wait",
                "prefill.emit"} <= charged
        kinds = {r["phase"] for r in engine.profiler.snapshot()["records"]}
        assert ("spec" if extra.get("speculative_k") else "decode") in kinds
        assert engine.profiler._stack == ["other"]  # nothing left open

    @pytest.mark.parametrize("attr", sorted(JIT_NAMES))
    def test_every_program_has_a_stable_name(self, spec_engine, attr):
        engine = spec_engine(speculative_k=2)
        fn = getattr(engine, attr)
        assert fn.__name__ == JIT_NAMES[attr]
        assert "unknown" not in fn.__name__

    def test_lowered_module_is_named_and_still_donates(self, spec_engine):
        """The name reaches the compiled module, and donate_argnames still
        resolves through the named partial (the cache is aliased)."""
        import jax.numpy as jnp

        engine = spec_engine()
        shape = engine.cache["k"].shape  # [L, B, S, K, hd]
        k = jnp.zeros((shape[0], 1, 8) + shape[3:], jnp.float32)
        low = engine._jit_insert.lower(engine.cache, k, k, jnp.int32(0),
                                       jnp.int32(3))
        ir = low.compiler_ir()
        assert str(ir.operation.attributes["sym_name"]) == \
            '"jit_insert_prefill"'
        text = low.as_text()
        assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
        assert "kv.insert" in low.as_text(debug_info=True)


class TestProfileReport:
    def payload(self):
        p = StepProfiler(capacity=32)
        p.note_dispatch("prefill", t0=0.6, wall_s=0.4, active=1,
                        total_slots=4, n_steps=8)
        p.note_dispatch("decode", t0=1.0, wall_s=0.2, active=2,
                        total_slots=4, n_steps=1)
        p.note_dispatch("decode", t0=1.3, wall_s=0.2, active=2,
                        total_slots=4, n_steps=1)
        p.note_idle()
        p.note_dispatch("decode", t0=2.0, wall_s=0.2, active=1,
                        total_slots=4, n_steps=1)
        return p.snapshot()

    def test_attribution_rows_sum_to_100(self):
        rows = profile_report.attribution_rows(self.payload())
        assert {r["bucket"] for r in rows} == {"dispatch", "host_sync",
                                               "idle"}
        assert sum(r["share_pct"] for r in rows) == pytest.approx(100.0,
                                                                  abs=1.0)

    def test_render_report_tables(self):
        out = profile_report.render_report(self.payload())
        assert "dispatch" in out and "host_sync" in out and "idle" in out
        assert "prefill" in out and "decode" in out
        assert "Recent decode dispatches" in out

    def test_extract_profile_accepts_dump_section(self):
        snap = self.payload()
        assert profile_report.extract_profile({"profile": snap}) is snap
        assert profile_report.extract_profile(snap) is snap
        with pytest.raises(ValueError):
            profile_report.extract_profile({"something": "else"})

    def test_extract_profile_accepts_blackbox_pod_map(self):
        """slo.write_blackbox stores profile as {pod: snapshot-or-error}
        — the documented 'render a dump' usage must accept that shape,
        skipping error markers and honoring --pod selection."""
        snap = self.payload()
        dump = {"profile": {"pod-b": snap,
                            "pod-a": {"error": "connection refused"}}}
        assert profile_report.extract_profile(dump) is snap
        assert profile_report.extract_profile(dump, pod="pod-b") is snap
        with pytest.raises(ValueError):
            profile_report.extract_profile(dump, pod="pod-a")
        with pytest.raises(ValueError):
            profile_report.extract_profile(
                {"profile": {"pod-a": {"error": "x"}}})


class TestPhaseReport:
    def payload(self):
        clock = FakeClock()
        p = StepProfiler(capacity=8, clock=clock)
        t0 = clock.now
        with p.phase("decode.stage") as ph:
            clock.tick(0.01)
            ph.to("decode.wait")
            clock.tick(0.06)
            ph.to("decode.readback")
            clock.tick(0.002)
            wall = clock.now - t0
            ph.to("decode.emit")
            clock.tick(0.001)
            p.note_dispatch("decode", t0, wall, active=2, total_slots=4)
        return p.snapshot()

    def test_thread_phase_rows_largest_first(self):
        rows = profile_report.thread_phase_rows(self.payload())
        assert rows[0]["phase"] == "decode.wait" and rows[0]["on"] == "device"
        assert len(rows) == 12
        assert sum(r["share_pct"] for r in rows) == pytest.approx(100.0,
                                                                  abs=0.01)
        # A payload from before the phase stack has no table, not an error.
        assert profile_report.thread_phase_rows(
            {"attribution": {"shares": {}}}) == []

    def test_report_prints_phase_table_and_decode_split(self):
        out = profile_report.render_report(self.payload())
        assert "Engine thread by phase" in out and "decode.wait" in out
        assert "stage_ms=10.0" in out and "wait_ms=60.0" in out
        split = profile_report.decode_split(self.payload())
        assert split["stage_ms"] + split["wait_ms"] + split["readback_ms"] \
            == pytest.approx(split["wall_ms"])

    def test_report_prints_staging_ops_per_dispatch(self):
        """``tpu:decode_stage_ops_total`` over the decode dispatches, from
        the same payload; none for a payload from before the counter."""
        clock = FakeClock()
        p = StepProfiler(capacity=8, clock=clock)
        for _ in range(4):
            p.note_stage_ops(2)
            p.note_dispatch("decode", clock.now, 0.01, active=1,
                            total_slots=4)
            clock.tick(0.02)
        p.note_stage_ops(2)  # a budget-zero scatter of the pipelined loop
        row = profile_report.stage_ops_row(p.snapshot())
        assert row == {"stage_ops": 10, "decode_dispatches": 4,
                       "ops_per_dispatch": 2.5}
        out = profile_report.render_report(p.snapshot())
        assert "Decode staging:" in out and "2.5" in out
        assert "tpu:decode_stage_ops_total 10" in render_profile(
            p.hist_state())
        old = p.snapshot()
        del old["hist"]["stage_ops"]
        assert profile_report.stage_ops_row(old) == {}
        assert "Decode staging" not in profile_report.render_report(old)

    def test_report_prints_the_decode_overlap(self):
        """``tpu:decode_blocks_overlapped_total``
        (``note_overlapped_block``): in ``/metrics``, in
        ``/debug/profile``'s ``hist``, and as a share of the decode blocks
        (plain and speculative) in the report; none for a payload from
        before the counter, 0 for a loop that overlaps nothing."""
        clock = FakeClock()
        p = StepProfiler(capacity=8, clock=clock)
        for kind, overlapped in (("decode", False), ("decode", True),
                                 ("spec", True), ("decode", True)):
            if overlapped:
                p.note_overlapped_block()
            p.note_dispatch(kind, clock.now, 0.01, active=3, total_slots=4)
            clock.tick(0.02)
        assert p.snapshot()["hist"]["blocks_overlapped"] == 3
        row = profile_report.overlap_row(p.snapshot())
        assert row == {"blocks_overlapped": 3, "decode_blocks": 4,
                       "overlapped_pct": 75.0}
        out = profile_report.render_report(p.snapshot())
        assert "Decode overlap" in out and "75.0" in out
        lines = render_profile(p.hist_state())
        assert "# TYPE tpu:decode_blocks_overlapped_total counter" in lines
        assert "tpu:decode_blocks_overlapped_total 3" in lines
        old = p.snapshot()
        del old["hist"]["blocks_overlapped"]
        assert profile_report.overlap_row(old) == {}
        assert "Decode overlap" not in profile_report.render_report(old)
        assert not any("blocks_overlapped" in ln
                       for ln in render_profile(old["hist"]))
        sync = StepProfiler(capacity=8, clock=clock)
        sync.note_dispatch("decode", clock.now, 0.01, active=1,
                           total_slots=4)
        assert profile_report.overlap_row(sync.snapshot()) == {
            "blocks_overlapped": 0, "decode_blocks": 1,
            "overlapped_pct": 0.0}

    def test_report_prints_adapter_rows_per_dispatch(self):
        """``tpu:lora_rows_total`` (``note_lora_rows``),
        ``tpu:lora_free_steps_total`` (``note_lora_free_steps``) and
        ``tpu:lora_target_reads_total`` (``note_lora_target_reads``): in
        ``/metrics``, in ``/debug/profile``'s ``hist``, and over the decode
        dispatches in the report; the third left out, then the second, then
        all, for payloads from before each counter."""
        clock = FakeClock()
        p = StepProfiler(capacity=8, clock=clock)
        for rows in (2, 0, 3, 1):
            p.note_lora_rows(rows)
            if not rows:
                p.note_lora_free_steps(1)
            else:
                p.note_lora_target_reads(2)
            p.note_dispatch("decode", clock.now, 0.01, active=3,
                            total_slots=4)
            clock.tick(0.02)
        assert p.snapshot()["hist"]["lora_rows"] == 6
        assert p.snapshot()["hist"]["lora_free_steps"] == 1
        assert p.snapshot()["hist"]["lora_target_reads"] == 6
        row = profile_report.lora_rows_row(p.snapshot())
        assert row == {"lora_rows": 6, "decode_dispatches": 4,
                       "rows_per_dispatch": 1.5, "lora_free_steps": 1,
                       "free_steps_per_dispatch": 0.25,
                       "lora_target_reads": 6,
                       "target_reads_per_dispatch": 1.5}
        out = profile_report.render_report(p.snapshot())
        assert "Adapter rows in the decode steps" in out and "1.5" in out
        assert "free_steps_per_dispatch" in out and "0.25" in out
        assert "target_reads_per_dispatch" in out
        lines = render_profile(p.hist_state())
        assert "# TYPE tpu:lora_target_reads_total counter" in lines
        assert "tpu:lora_target_reads_total 6" in lines
        old = p.snapshot()
        del old["hist"]["lora_target_reads"]
        assert profile_report.lora_rows_row(old) == {
            "lora_rows": 6, "decode_dispatches": 4, "rows_per_dispatch": 1.5,
            "lora_free_steps": 1, "free_steps_per_dispatch": 0.25}
        assert "target_reads" not in profile_report.render_report(old)
        assert not any("lora_target_reads" in ln
                       for ln in render_profile(old["hist"]))
        assert "# TYPE tpu:lora_rows_total counter" in lines
        assert "tpu:lora_rows_total 6" in lines
        assert "# TYPE tpu:lora_free_steps_total counter" in lines
        assert "tpu:lora_free_steps_total 1" in lines
        del old["hist"]["lora_free_steps"]
        assert profile_report.lora_rows_row(old) == {
            "lora_rows": 6, "decode_dispatches": 4, "rows_per_dispatch": 1.5}
        assert "free_steps" not in profile_report.render_report(old)
        assert not any("lora_free_steps" in ln
                       for ln in render_profile(old["hist"]))
        del old["hist"]["lora_rows"]
        assert profile_report.lora_rows_row(old) == {}
        assert "Adapter rows" not in profile_report.render_report(old)
        assert not any("lora_rows" in ln
                       for ln in render_profile(old["hist"]))


    @pytest.mark.parametrize("asked", [(0, 0, 0, 0), (1, 0, 4, 0)],
                             ids=["nobody-asks", "two-blocks-ask"])
    def test_report_prints_logprob_steps_per_dispatch(self, asked):
        """``tpu:logprob_steps_total`` (``note_logprob_steps``): in
        ``/metrics``, in ``/debug/profile``'s ``hist`` and over the decode
        dispatches in the report, 0 included; left out for a payload from
        before the counter."""
        clock = FakeClock()
        p = StepProfiler(capacity=8, clock=clock)
        for steps in asked:
            if steps:
                p.note_logprob_steps(steps)
            p.note_dispatch("decode", clock.now, 0.01, active=3,
                            total_slots=4)
            clock.tick(0.02)
        total = sum(asked)
        assert p.snapshot()["hist"]["logprob_steps"] == total
        assert profile_report.logprob_steps_row(p.snapshot()) == {
            "logprob_steps": total, "decode_dispatches": 4,
            "logprob_steps_per_dispatch": total / 4}
        out = profile_report.render_report(p.snapshot())
        assert "asked for logprobs" in out
        assert "logprob_steps_per_dispatch" in out
        lines = render_profile(p.hist_state())
        assert "# TYPE tpu:logprob_steps_total counter" in lines
        assert f"tpu:logprob_steps_total {total}" in lines
        old = p.snapshot()
        del old["hist"]["logprob_steps"]
        assert profile_report.logprob_steps_row(old) == {}
        assert "logprobs" not in profile_report.render_report(old)
        assert not any("logprob_steps" in ln
                       for ln in render_profile(old["hist"]))

    def test_report_prints_latent_rows_per_dispatch(self):
        """``tpu:latent_kv_positions_total`` (``note_latent_positions``):
        in ``/metrics`` always, in the report only for a latent model."""
        clock = FakeClock()
        p = StepProfiler(capacity=8, clock=clock)
        for positions in (100, 102, 104, 106):
            p.note_latent_positions(positions)
            p.note_dispatch("decode", clock.now, 0.01, active=3,
                            total_slots=4)
            clock.tick(0.02)
        assert p.snapshot()["hist"]["latent_positions"] == 412
        assert profile_report.latent_positions_row(p.snapshot()) == {
            "latent_positions": 412, "decode_dispatches": 4,
            "positions_per_dispatch": 103.0}
        out = profile_report.render_report(p.snapshot())
        assert "Latent cache rows read by the decode steps:" in out
        assert "tpu:latent_kv_positions_total 412" in render_profile(
            p.hist_state())
        lanes = StepProfiler(capacity=8, clock=clock)
        lanes.note_dispatch("decode", clock.now, 0.01, active=3,
                            total_slots=4)
        assert profile_report.latent_positions_row(lanes.snapshot()) == {}
        assert "Latent cache" not in profile_report.render_report(
            lanes.snapshot())
        assert "tpu:latent_kv_positions_total 0" in render_profile(
            lanes.hist_state())


    def test_report_prints_grid_steps_per_dispatch(self):
        """``tpu:decode_attn_grid_steps_total`` (``note_attn_grid_steps``):
        in ``/metrics`` always, in the report where a kernel's schedule
        counted any."""
        clock = FakeClock()
        p = StepProfiler(capacity=8, clock=clock)
        for steps in (3, 4, 4, 5):
            p.note_attn_grid_steps(steps)
            p.note_dispatch("decode", clock.now, 0.01, active=3,
                            total_slots=4)
            clock.tick(0.02)
        assert p.snapshot()["hist"]["attn_grid_steps"] == 16
        assert profile_report.attn_grid_steps_row(p.snapshot()) == {
            "attn_grid_steps": 16, "decode_dispatches": 4,
            "steps_per_dispatch": 4.0}
        out = profile_report.render_report(p.snapshot())
        assert "Grid steps of the decode-attention kernel" in out
        assert "tpu:decode_attn_grid_steps_total 16" in render_profile(
            p.hist_state())
        none = StepProfiler(capacity=8, clock=clock)
        none.note_dispatch("decode", clock.now, 0.01, active=3,
                           total_slots=4)
        assert profile_report.attn_grid_steps_row(none.snapshot()) == {}
        assert "Grid steps" not in profile_report.render_report(
            none.snapshot())
        assert "tpu:decode_attn_grid_steps_total 0" in render_profile(
            none.hist_state())
        old = p.snapshot()
        del old["hist"]["attn_grid_steps"]  # a payload from before PR 47
        assert profile_report.attn_grid_steps_row(old) == {}
        assert not any("decode_attn_grid_steps" in ln
                       for ln in render_profile(old["hist"]))

    def test_chunk_programs_add_their_attends_grid_steps(self):
        """``tpu:chunk_attn_grid_steps_total`` (``note_prompt_program``'s
        fourth argument): a chunk program adds what the engine reckoned
        from the shapes, the other prompt programs nothing; in ``/metrics``
        always, and not rendered from a payload that predates it."""
        p = StepProfiler(capacity=8, clock=FakeClock())
        p.note_prompt_program("chunk", 900, 124, 1488)
        p.note_prompt_program("prefill", 100, 28)
        p.note_prompt_program("chunk", 1024, 0, 1488)
        assert p.hist_state()["chunk_attn_grid_steps"] == 2976
        assert "tpu:chunk_attn_grid_steps_total 2976" in render_profile(
            p.hist_state())
        assert "tpu:chunk_attn_grid_steps_total 0" in render_profile(
            StepProfiler(capacity=8, clock=FakeClock()).hist_state())
        old = p.hist_state()
        del old["chunk_attn_grid_steps"]  # a payload from before PR 59
        assert not any("chunk_attn_grid_steps" in ln
                       for ln in render_profile(old))


class TestXplaneGaps:
    """``--xplane``'s reduction, on a hand-made event list: device busy
    0-10, 14-20 and 30-40; the engine thread in decode.wait 0-9, then
    readback, emit (with a nested prefill.emit), stage with its enqueue."""

    OPS = [(0, 10), (14, 6), (30, 10), (2, 3)]  # the last one is nested
    ANN = [("engine.decode.wait", 0, 9), ("engine.decode.readback", 9, 3),
           ("engine.decode.emit", 12, 10), ("engine.prefill.emit", 21, 1),
           ("engine.decode.stage", 22, 10),
           ("engine.decode.enqueue", 28, 1)]

    def test_self_time_segments_take_children_out(self):
        segs = profile_report.self_time_segments(
            [("a", 0, 10), ("b", 2, 3), ("c", 3, 1), ("d", 12, 2)])
        assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"),
                        (5, 10, "a"), (12, 14, "d")]

    def test_gaps_go_to_the_innermost_phase(self):
        t = profile_report.gaps_by_phase(self.OPS, self.ANN, top=2)
        assert t["n_gaps"] == 2
        assert t["idle_s"] == pytest.approx(14e-9)
        assert t["idle_pct"] == pytest.approx(35.0)
        longest = t["longest"][0]
        assert longest["gap_ms"] == pytest.approx(10e-6)
        # 20-30: emit 20-21, prefill.emit 21-22, stage 22-28 and 29-30,
        # the enqueue 28-29.
        assert longest["phases_ms"] == pytest.approx(
            {"decode.stage": 7e-6, "decode.emit": 1e-6,
             "prefill.emit": 1e-6, "decode.enqueue": 1e-6})
        # 10-14: readback 10-12, emit 12-14.
        assert t["longest"][1]["phases_ms"] == pytest.approx(
            {"decode.readback": 2e-6, "decode.emit": 2e-6})
        assert sum(t["total_ms"].values()) == pytest.approx(14e-6)
        assert t["total_ms"]["decode.stage"] == pytest.approx(7e-6)

    def test_time_under_no_annotation_is_other(self):
        t = profile_report.gaps_by_phase([(0, 1), (5, 1)],
                                         [("engine.idle", 2, 1)])
        assert t["total_ms"] == pytest.approx(
            {"other": 3e-6, "idle": 1e-6})
        assert "error" in profile_report.gaps_by_phase([], self.ANN)

    def test_op_scope_reads_program_and_innermost_scope(self):
        stats = [7, "fusion.3", "jit(decode_block)/while/body/closed_call/"
                 "sample/sample.topk_sort/jit(sort)/sort:"]
        assert profile_report.op_scope(stats) == (
            "decode_block", "sample/sample.topk_sort",
            "sample.topk_sort/jit(sort)/sort")
        assert profile_report.op_scope(
            ["jit(prefill)/while/body/attn.qkv/lora/dot_general:"]
        ) == ("prefill", "attn.qkv/lora", "attn.qkv/lora/dot_general")
        assert profile_report.op_scope(
            ["jit(decode_block)/while/body/dynamic_update_slice:"]) == (
            "decode_block", "", "while/body/dynamic_update_slice")
        assert profile_report.op_scope([3, "copy.1", ""]) == ("", "", "")

    def test_ops_by_scope_sums(self):
        path = "jit(decode_block)/jit(main)/while/body/{}/op"
        out = profile_report.ops_by_scope([
            ("fusion.1", 4e6, [path.format("mlp")]),
            ("fusion.1", 2e6, [path.format("mlp")]),
            ("sort.8", 3e6, [path.format("sample/sample.topk_sort")]),
            ("fusion.9", 2e6, [path.format("moe.dispatch/moe.experts")]),
            ("copy.2", 1e6, []),
        ])
        assert out["ops"][0] == {"op": "fusion.1", "program": "decode_block",
                                 "scope": "mlp", "where": "body/mlp/op",
                                 "device_ms": 6.0}
        assert out["scopes_ms"] == {
            "mlp": 6.0, "sample/sample.topk_sort": 3.0,
            "moe.dispatch/moe.experts": 2.0, "(none)": 1.0}

    def test_render_xplane(self):
        out = profile_report.render_xplane({
            "plane": "/device:TPU:0", "thread": "engine", "ops": self.OPS,
            "op_events": [("fusion.1", 4e6, [])], "modules": [
                "jit_decode_block(1)"], "annotations": self.ANN})
        assert "DEVICE IDLE GAP -> ENGINE PHASE" in out
        assert "decode.stage" in out and "jit_decode_block" in out

    def test_read_xplane_from_the_wire(self, tmp_path):
        """A hand-encoded XSpace: one TPU plane (two operations, one with
        a tf_op stat held by reference, and a program) and a host plane
        whose busier thread carries the annotations."""
        def vi(n):
            out = bytearray()
            while True:
                out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
                n >>= 7
                if not n:
                    return bytes(out)

        def num(field, n):
            return vi(field << 3) + vi(n)

        def ld(field, payload):
            payload = payload.encode() if isinstance(payload, str) else payload
            return vi(field << 3 | 2) + vi(len(payload)) + payload

        def entry(field, key, msg):
            return ld(field, num(1, key) + ld(2, msg))

        def event(mid, offset_ps, dur_ps, stats=b""):
            return ld(4, num(1, mid) + num(2, offset_ps) + num(3, dur_ps)
                      + stats)

        path = "jit(decode_block)/while/body/attn.qkv/dot_general:"
        device = (
            ld(2, "/device:TPU:0")
            + entry(5, 1, num(1, 1) + ld(2, "tf_op"))
            + entry(5, 2, num(1, 2) + ld(2, path))
            + entry(4, 10, ld(2, "%fusion.7 = bf16[8] fusion(%x)")
                    + ld(5, num(1, 1) + num(7, 2)))
            + entry(4, 11, ld(2, "%copy.3 = bf16[8] copy(%y)"))
            + entry(4, 12, ld(2, "jit_decode_block(42)"))
            + ld(3, ld(2, "XLA Ops") + num(3, 1000)
                 + event(10, 0, 4_000_000) + event(11, 9_000_000, 1_000_000))
            + ld(3, ld(2, "XLA Modules") + num(3, 1000)
                 + event(12, 0, 10_000_000)))
        host = (
            ld(2, "/host:CPU")
            + entry(4, 1, ld(2, "engine.decode.wait"))
            + entry(4, 2, ld(2, "engine.decode.stage"))
            + entry(4, 3, ld(2, "PjitFunction(decode_block)"))
            + entry(4, 4, ld(2, "engine.prefill.enqueue"))
            + entry(5, 1, num(1, 1) + ld(2, "request_id"))
            + entry(5, 2, num(1, 2) + ld(2, "bucket"))
            + entry(5, 3, num(1, 3) + ld(2, "name"))
            + ld(3, ld(2, "other-thread") + num(3, 1000)
                 + event(1, 0, 1_000_000))
            + ld(3, ld(2, "engine-thread") + num(3, 1000)
                 + event(1, 0, 4_000_000) + event(2, 4_000_000, 6_000_000)
                 + event(3, 5_000_000, 1_000_000,
                         ld(4, num(1, 3) + ld(5, "jit_decode_block")))
                 + event(4, 6_000_000, 500_000,
                         ld(4, num(1, 1) + ld(5, "ab12"))
                         + ld(4, num(1, 2) + num(3, 128)))))
        f = tmp_path / "t.xplane.pb"
        f.write_bytes(ld(1, device) + ld(1, host))
        t = profile_report.read_xplane(str(f))
        assert t["plane"] == "/device:TPU:0" and t["thread"] == "engine-thread"
        assert t["modules"] == ["jit_decode_block(42)"]
        # picoseconds on the wire, nanoseconds out
        assert t["ops"] == [(1000.0, 4000.0), (10000.0, 1000.0)]
        assert t["op_events"] == [("fusion.7", 4000.0, [path]),
                                  ("copy.3", 1000.0, [""])]
        assert t["annotations"] == [
            ("engine.decode.wait", 1000.0, 4000.0),
            ("engine.decode.stage", 5000.0, 6000.0),
            ("engine.prefill.enqueue", 7000.0, 500.0)]
        # an annotation's own metadata, not another host event's
        assert t["notes"] == [(7000.0, {"request_id": "ab12", "bucket": 128})]
        table = profile_report.gaps_by_phase(t["ops"], t["annotations"],
                                             notes=t["notes"])
        assert table["total_ms"] == pytest.approx(
            {"decode.stage": 4.5e-3, "prefill.enqueue": 0.5e-3})
        assert table["longest"][0]["notes"] == [
            {"request_id": "ab12", "bucket": 128}]
        assert "request_id=ab12 bucket=128" in profile_report.render_xplane(t)
        assert profile_report.ops_by_scope(t["op_events"])["ops"][0][
            "scope"] == "attn.qkv"
        with pytest.raises(ValueError):
            list(profile_report._fields(memoryview(b"\x0b")))  # a group

    def test_scopes_are_the_model_codes(self):
        """The tool's scope list is the one the model code uses."""
        import re

        used = set()
        for rel in ("llm_instance_gateway_tpu/models/transformer.py",
                    "llm_instance_gateway_tpu/models/mla.py",
                    "llm_instance_gateway_tpu/models/ssm.py",
                    "llm_instance_gateway_tpu/models/shortconv.py",
                    "llm_instance_gateway_tpu/models/kda.py",
                    "llm_instance_gateway_tpu/models/paged.py",
                    "llm_instance_gateway_tpu/models/lora.py",
                    "llm_instance_gateway_tpu/ops/layers.py",
                    "llm_instance_gateway_tpu/server/sampling.py",
                    "llm_instance_gateway_tpu/server/engine.py"):
            used |= set(re.findall(r'named_scope\("([^"]+)"\)',
                                   (REPO / rel).read_text()))
        assert used == set(profile_report.SCOPES)


class TestReportOfALiveProfile:
    """The report's arithmetic over a profile taken here from the module's
    engine: the attribution table's shares sum to 100% +- 1%, and the
    host-sync delta against an earlier payload's shares."""

    def profile(self, profiled_engine):
        engine, _ = profiled_engine
        run_requests(engine, n=2)
        # through JSON, as /debug/profile ships it, under the dump's key
        doc = json.loads(json.dumps({"profile": engine.profiler.snapshot()}))
        return profile_report.extract_profile(doc)

    def test_live_profile_renders_and_sums(self, profiled_engine):
        profile = self.profile(profiled_engine)
        rows = profile_report.attribution_rows(profile)
        total = sum(r["share_pct"] for r in rows)
        assert total == pytest.approx(100.0, abs=1.0), rows
        att = profile["attribution"]
        assert att["dispatches"] > 0 and att["dispatch_seconds"] > 0
        out = profile_report.render_report(profile)
        assert "ENGINE STEP-TIMELINE ATTRIBUTION" in out

    def test_host_sync_delta_against_previous_shares(self, profiled_engine):
        profile = self.profile(profiled_engine)
        cur = profile["attribution"]["shares"]["host_sync"]
        # (0 where every block of the run was staged behind another)
        assert 0.0 <= cur < 1.0
        previous = {"shares": {"host_sync": cur + (1.0 - cur) / 2}}
        delta = profile_report.host_sync_delta(profile, previous)
        assert delta is not None and delta["improved"], delta
        assert delta["current_pct"] < delta["previous_pct"]
        assert delta["delta_pp"] == pytest.approx(
            delta["current_pct"] - delta["previous_pct"], abs=1e-3)
        out = profile_report.render_report(profile, previous=previous)
        assert "Host-sync share vs previous baseline" in out
        assert "improved" in out
        # a full payload serves as ``previous`` too (--baseline FILE)
        same = profile_report.host_sync_delta(profile, profile)
        assert same["delta_pp"] == 0.0 and not same["improved"]
        assert profile_report.host_sync_delta(profile, None) is None
