"""Observability layer: tracer / metrics / watchdog units, and the engine
integration contract — steady-state serving after calibration performs ZERO
unexpected recompiles (armed watchdog passes), and an injected out-of-lattice
shape demonstrably fires it."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.registry import get_smoke_config
from repro.models import init_lm
from repro.obs import view
from repro.obs.trace import NULL_SPAN, Tracer
from repro.serving.engine import (Completion, Engine, EngineStats,
                                  synthetic_requests)
from repro.serving.engine.request import Request
from repro.tuning.cache import TunedConfig
from repro.tuning.measure import wall_us


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with obs disabled and empty — the rest of
    the suite must never see leaked spans/metrics."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = get_smoke_config("internlm2-1.8b")
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


class TestTracer:
    def test_disabled_is_shared_noop(self):
        s = obs.span("anything", rid=1)
        assert s is NULL_SPAN and s.dur_s == 0.0
        with s:
            pass
        obs.instant("nothing")
        assert obs.get_tracer().events() == []

    def test_span_nesting_and_chrome_validity(self):
        obs.enable(annotate_device=False)
        with obs.span("outer", cat="engine", rid=7):
            with obs.span("inner", cat="sample"):
                pass
        evs = obs.get_tracer().events()
        by_name = {e["name"]: e for e in evs}
        inner, outer = by_name["inner"], by_name["outer"]
        # depth comes from the per-thread span stack; containment from the
        # shared monotonic clock
        assert outer["args"]["depth"] == 0 and inner["args"]["depth"] == 1
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert outer["args"]["rid"] == 7

        chrome = obs.get_tracer().to_chrome()
        # valid Chrome trace-event JSON: metadata header + X events with
        # ts/dur/pid/tid, JSON-round-trippable, displayTimeUnit present
        assert chrome["displayTimeUnit"] == "ms"
        assert chrome["traceEvents"][0]["ph"] == "M"
        for e in chrome["traceEvents"][1:]:
            assert e["ph"] in ("X", "i")
            assert e["ts"] >= 0 and "pid" in e and "tid" in e
            if e["ph"] == "X":
                assert e["dur"] >= 0
        assert json.loads(json.dumps(chrome)) == chrome

    def test_instant_and_bounded_buffer(self):
        tr = Tracer(capacity=4, annotate_device=False)
        for i in range(6):
            tr.instant("tick", i=i)
        evs = tr.events()
        assert len(evs) == 4 and tr.dropped == 2
        assert all(e["ph"] == "i" and e["s"] == "t" for e in evs)
        assert evs[0]["args"]["i"] == 2  # oldest two fell off

    def test_span_ids_and_clock_origin(self):
        obs.enable(annotate_device=False)
        with obs.span("a", rid=3):
            with obs.span("b"):
                pass
        with obs.span("c") as c:
            pass
        tr = obs.get_tracer()
        ids = {e["name"]: e["args"]["span_id"] for e in tr.events()}
        # numbered in the order the spans were entered, one per span
        assert ids == {"a": ids["a"], "b": ids["a"] + 1, "c": ids["a"] + 2}
        assert c.span_id == ids["c"]
        other = tr.to_chrome()["otherData"]
        assert other["perf_counter_origin_s"] == tr._t0

    def test_save_is_loadable(self, tmp_path):
        obs.enable(annotate_device=False)
        with obs.span("step"):
            pass
        path = tmp_path / "trace.json"
        obs.get_tracer().save(str(path))
        trace = json.loads(path.read_text())
        assert any(e["ph"] == "X" and e["name"] == "step"
                   for e in trace["traceEvents"])


class TestMetrics:
    def test_instruments(self):
        obs.counter("t.count").inc()
        obs.counter("t.count").inc(2)
        obs.gauge("t.depth").set(5)
        h = obs.histogram("t.lat")
        h.observe_many([1.0, 2.0, 3.0, 4.0])
        snap = obs.get_metrics().snapshot()
        assert snap["counters"]["t.count"] == 3
        assert snap["gauges"]["t.depth"] == 5.0
        s = snap["histograms"]["t.lat"]
        assert s["count"] == 4 and s["sum"] == 10.0 and s["min"] == 1.0
        assert s["p50"] == pytest.approx(2.5)
        json.dumps(snap)  # snapshot must be JSON-serializable as-is

    def test_prometheus_text(self):
        obs.counter("engine.tokens_generated").inc(42)
        obs.gauge("engine.queue_depth").set(3)
        obs.histogram("engine.decode_step_s").observe(0.25)
        text = obs.get_metrics().to_prometheus()
        assert "# TYPE engine_tokens_generated counter" in text
        assert "engine_tokens_generated 42" in text
        assert "# TYPE engine_queue_depth gauge" in text
        assert 'engine_decode_step_s{quantile="0.5"} 0.25' in text
        assert "engine_decode_step_s_count 1" in text


class TestCompileWatch:
    def test_records_arming_and_mirror(self):
        obs.enable(annotate_device=False)

        def watched(x):
            return x * 2.0 + 1.0

        f = jax.jit(watched)
        with obs.CompileWatch() as watch:
            jax.block_until_ready(f(jnp.ones((4,), jnp.float32)))
            recs = [r for r in watch.records if "watched" in r.name]
            assert recs and recs[0].wall_s > 0 and not recs[0].armed
            n = len(watch.records)
            # jit cache hit: same shape must not record a compile
            jax.block_until_ready(f(jnp.ones((4,), jnp.float32)))
            assert len(watch.records) == n
            watch.check()  # not armed -> never raises

            watch.arm()
            with pytest.raises(obs.UnexpectedCompile):
                f(jnp.ones((5,), jnp.float32))
            assert watch.violations and watch.violations[-1].armed
            with pytest.raises(obs.UnexpectedCompile):
                watch.check()
            watch.disarm()

            d = watch.to_json()
            assert d["records"] and d["violations"]
            assert d["backend_compiles"] >= 1
        # mirrored into the metrics registry while obs was enabled
        snap = obs.get_metrics().snapshot()
        assert snap["counters"]["compile.count"] >= 2
        assert snap["counters"]["compile.violations"] >= 1


class TestDrift:
    def test_report_normalizes_by_median_ratio(self):
        mon = obs.DriftMonitor(hw_name="test_hw")
        mon.add_site("a", 0.001)
        mon.add_site("b", 0.001)
        for _ in range(3):
            mon.observe("a", 0.002)  # ratio 2.0
        mon.observe("b", 0.001)      # ratio 1.0
        rows = {r["site"]: r for r in mon.report()}
        assert rows["a"]["ratio"] == pytest.approx(2.0)
        assert rows["b"]["ratio"] == pytest.approx(1.0)
        med = 1.5  # median of [2.0, 1.0]
        assert rows["a"]["rel_drift"] == pytest.approx(2.0 / med)
        assert rows["b"]["rel_drift"] == pytest.approx(1.0 / med)

    def test_unknown_site_and_empty_sites(self, tmp_path):
        mon = obs.DriftMonitor()
        mon.add_site("never_observed", 0.5)
        mon.observe("surprise", 0.1)  # auto-created, predicted 0
        rows = mon.report()
        assert [r["site"] for r in rows] == ["surprise"]
        assert rows[0]["ratio"] is None and rows[0]["rel_drift"] is None
        mon.save(str(tmp_path / "drift.json"))
        d = json.loads((tmp_path / "drift.json").read_text())
        assert d["rows"][0]["site"] == "surprise"


class TestEngineStatsSplits:
    def _comp(self, rid, cached):
        return Completion(rid=rid, prompt_len=8, tokens=[1, 2],
                          arrival_s=0.0, first_token_s=0.1, done_s=0.2,
                          itl_s=[0.05], cached_tokens=cached)

    def test_all_cold(self):
        st = EngineStats.collect([self._comp(0, 0), self._comp(1, 0)], 1.0)
        assert st.ttft_hit_p50_s is None
        assert st.ttft_cold_p50_s == pytest.approx(0.1)

    def test_all_hit(self):
        st = EngineStats.collect([self._comp(0, 4)], 1.0)
        assert st.ttft_cold_p50_s is None
        assert st.ttft_hit_p50_s == pytest.approx(0.1)
        assert st.cache_hit_requests == 1

    def test_empty_run(self):
        st = EngineStats.collect([], 0.0)
        assert st.tok_s == 0.0
        assert st.ttft_hit_p50_s is None and st.ttft_cold_p50_s is None
        json.dumps(st.to_json())


class TestMeasureSamples:
    def test_return_samples(self):
        def f(x):
            return x + 1.0

        x = jnp.ones((8,), jnp.float32)
        mean, samples = wall_us(f, x, iters=3, warmup=1, return_samples=True)
        assert len(samples) == 3 and all(s > 0 for s in samples)
        assert mean == pytest.approx(sum(samples) / 3)
        # default path unchanged: a bare float
        assert isinstance(wall_us(f, x, iters=2, warmup=1), float)

    def test_tuned_config_std_roundtrip(self):
        cfg = TunedConfig(op="matmul", shape=(8, 8, 8), dtype="float32",
                          hw_name="test", blocks={"block_m": 8},
                          time_us=10.0, time_us_std=1.5)
        back = TunedConfig.from_json(cfg.to_json())
        assert back.time_us_std == 1.5
        # pre-std cache files load with the 0.0 default
        old = {k: v for k, v in cfg.to_json().items() if k != "time_us_std"}
        assert TunedConfig.from_json(old).time_us_std == 0.0


class TestEngineObservability:
    """The acceptance contract, end to end on the smoke model: calibrate ->
    arm -> serve with zero unexpected recompiles, spans/metrics/drift line up
    with the engine's own counters, the dump renders, and an out-of-lattice
    shape fires the armed watchdog."""

    def test_steady_state_and_armed_fire(self, smoke_lm, tmp_path):
        cfg, params = smoke_lm
        eng = Engine(params, cfg, max_batch=2, max_prompt=16, max_new=8)
        watch = obs.CompileWatch().install()
        try:
            eng.calibrate_step_s()  # warms every (bucket, decode) program
            warm = len(watch.records)
            assert warm >= eng.policy.num_programs  # prefills + decode (+aux)

            obs.enable(annotate_device=False)
            obs.reset()  # counters below are per-run, not per-process
            # the engine builds no drift monitor: the caller attaches one
            assert eng.drift is None
            eng.drift = obs.DriftMonitor.for_engine(eng.cfg, eng.policy,
                                                    eng.hw)
            watch.arm()
            reqs = synthetic_requests(4, pattern="burst", min_prompt=4,
                                      max_prompt=16, min_new=2, max_new=8,
                                      vocab=cfg.vocab_size, seed=3)
            done, stats = eng.run(reqs)
            watch.check()  # ZERO unexpected recompiles in steady state
            assert len(watch.records) == warm and not watch.violations
            watch.disarm()

            # spans mirror the engine's own counters one-to-one
            evs = obs.get_tracer().events()
            spans = [e for e in evs if e["ph"] == "X"]
            by = lambda n: [e for e in spans if e["name"] == n]
            assert len(by("decode_step")) == stats.decode_steps > 0
            assert len(by("prefill")) == stats.prefills == len(done)
            assert len(by("admit")) == stats.prefills
            # every sample span nests inside an admit or decode_step interval
            parents = by("decode_step") + by("admit")
            for s in by("sample"):
                assert s["args"]["depth"] >= 1
                assert any(p["ts"] <= s["ts"] and
                           s["ts"] + s["dur"] <= p["ts"] + p["dur"]
                           for p in parents)

            snap = obs.get_metrics().snapshot()
            assert snap["counters"]["engine.tokens_generated"] == \
                stats.total_generated == sum(len(c.tokens) for c in done)
            assert snap["counters"]["engine.decode_steps"] == stats.decode_steps
            assert snap["counters"]["engine.requests_completed"] == len(done)
            assert snap["histograms"]["engine.decode_step_s"]["count"] == \
                stats.decode_steps

            # the attached monitor has one site, fed once per decode step
            rows = {r["site"]: r for r in eng.drift.report()}
            assert list(rows) == ["decode_step"]
            assert rows["decode_step"]["count"] == stats.decode_steps

            # the dump round-trips through export_all and the view CLI
            dump = str(tmp_path / "dump")
            paths = obs.export_all(dump, drift=eng.drift, watch=watch)
            assert sorted(paths) == ["compiles", "drift", "metrics",
                                     "prometheus", "trace"]
            trace = json.loads(open(paths["trace"]).read())
            assert any(e["ph"] == "X" and e["name"] == "decode_step"
                       for e in trace["traceEvents"])
            lines = view.render_summary(dump)
            text = "\n".join(lines)
            assert "decode_step" in text and "Compiles" in text
            assert view.main([dump]) == 0

            # inject an out-of-lattice shape: the armed watchdog must fire
            # from inside the offending jit call
            contig = eng.pool.gather(0)     # a row's cache view, as admit
            watch.arm()
            bucket = eng.policy.prompt_buckets[0]
            bad = np.zeros((1, bucket + 3), np.int32)  # width off the lattice
            with pytest.raises(obs.UnexpectedCompile):
                eng._prefills[bucket](params, jnp.asarray(bad),
                                      jnp.asarray(1, jnp.int32),
                                      jnp.asarray(0, jnp.int32), contig)
            assert watch.violations
            watch.disarm()
        finally:
            watch.uninstall()


LOOP_SPANS = {"schedule", "admit", "idle_wait", "decode_step", "bookkeep"}


def _shared_prefix_requests(n, *, vocab, gap_s=0.0, seed=5):
    """Prompts of 12-28 tokens, the first 16 shared by every other request,
    due `gap_s` apart."""
    rng = np.random.default_rng(seed)
    head = rng.integers(1, vocab, 16, dtype=np.int32)
    reqs = []
    for i in range(n):
        own = rng.integers(1, vocab, int(rng.integers(12, 29)), dtype=np.int32)
        toks = np.concatenate([head, own[:12]]) if i % 2 else own
        reqs.append(Request(rid=i, tokens=toks,
                            max_new_tokens=int(rng.integers(2, 9)),
                            arrival_s=i * gap_s))
    return reqs


def _tiny_engine(smoke_lm, kv_dtype="auto"):
    cfg, params = smoke_lm
    return Engine(params, cfg, max_batch=3, max_prompt=32, max_new=8,
                  block_size=8, num_blocks=64, kv_dtype=kv_dtype)


class TestEngineLoopTracing:
    """Tracing changes what is recorded, never what the engine does: the
    same device syncs and host readbacks with obs on and off, top-level
    spans that account for the run's wall time, and counters that agree
    with what the completions and the spans say happened."""

    @pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
    def test_same_host_syncs_with_obs_on_and_off(self, smoke_lm,
                                                 kv_dtype, monkeypatch):
        from jax._src.array import ArrayImpl
        eng = _tiny_engine(smoke_lm, kv_dtype)
        reqs = _shared_prefix_requests(6, vocab=smoke_lm[0].vocab_size)
        eng.run(reqs)                               # compile everything
        counts = {"sync": 0, "readback": 0}
        block = jax.block_until_ready
        method = ArrayImpl.block_until_ready
        value = ArrayImpl._value
        buffer = ArrayImpl.__buffer__

        def counting_block(x):
            counts["sync"] += 1
            return block(x)

        def counting_method(self):
            counts["sync"] += 1
            return method(self)

        def counting_value(self):
            if self._npy_value is None:     # a copy from the device
                counts["readback"] += 1
            return value.fget(self)

        def counting_buffer(self, flags):   # NumPy's zero-copy host view
            counts["readback"] += 1
            return buffer(self, flags)

        monkeypatch.setattr(jax, "block_until_ready", counting_block)
        monkeypatch.setattr(ArrayImpl, "block_until_ready", counting_method)
        monkeypatch.setattr(ArrayImpl, "_value", property(counting_value))
        monkeypatch.setattr(ArrayImpl, "__buffer__", counting_buffer)
        seen = {}
        for on in (False, True):
            if on:
                obs.enable(annotate_device=True)
            counts.update(sync=0, readback=0)
            done, _ = eng.run(reqs)
            seen[on] = dict(counts)
            assert all(c.ok for c in done)
        assert seen[True] == seen[False]
        assert seen[False]["readback"] > 0
        assert eng.drift is None

    def test_loop_spans_cover_the_run(self, smoke_lm):
        eng = _tiny_engine(smoke_lm)
        warm = _shared_prefix_requests(6, vocab=smoke_lm[0].vocab_size)
        eng.run(warm)
        obs.enable(annotate_device=False)
        # due apart, so the pool drains and the loop waits between them
        reqs = _shared_prefix_requests(6, vocab=smoke_lm[0].vocab_size,
                                       gap_s=0.05, seed=6)
        done, stats = eng.run(reqs)
        spans = [e for e in obs.get_tracer().events() if e["ph"] == "X"]
        top = [e for e in spans if e["args"]["depth"] == 0]
        assert {e["name"] for e in top} == LOOP_SPANS
        covered_s = sum(e["dur"] for e in top) * 1e-6
        assert covered_s >= 0.95 * stats.wall_s
        # decode_step and bookkeep are siblings, once per step each;
        # decode_inputs sits inside decode_step
        by = lambda n: [e for e in spans if e["name"] == n]
        assert len(by("decode_step")) == len(by("bookkeep")) == \
            stats.decode_steps
        assert all(e["args"]["depth"] == 1 for e in by("decode_inputs"))
        assert len(by("decode_inputs")) == stats.decode_steps

    @pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
    def test_counters_match_completions_and_spans(self, smoke_lm,
                                                  kv_dtype):
        eng = _tiny_engine(smoke_lm, kv_dtype)
        reqs = _shared_prefix_requests(8, vocab=smoke_lm[0].vocab_size)
        obs.enable(annotate_device=False)
        done, stats = eng.run(reqs)
        assert all(c.ok for c in done) and stats.preemptions == 0
        assert sum(c.cached_tokens for c in done) > 0
        ran = [c.prompt_len - c.cached_tokens for c in done]
        live = [e["args"]["live"] for e in obs.get_tracer().events()
                if e["name"] == "decode_step"]
        counters = obs.get_metrics().snapshot()["counters"]
        assert counters["engine.prefill_tokens"] == sum(ran)
        assert counters["engine.prefill_bucket_tokens"] == \
            sum(eng.policy.prompt_bucket(n) for n in ran)
        assert counters["engine.decode_rows_live"] == sum(live)
        assert counters["engine.decode_steps"] == len(live)

    def test_decode_kv_pages_counts_the_live_pages(self, smoke_lm):
        """`engine.decode_kv_pages` is the pages the paged-decode kernel
        reads: ceil(kv length / block_size) over the live rows of each
        decode call, the kv length counting the token the call writes."""
        eng = _tiny_engine(smoke_lm)
        reqs = _shared_prefix_requests(8, vocab=smoke_lm[0].vocab_size)
        decode, pages = eng._decode, []

        def recording_decode(*args):
            pos = np.asarray(args[3])           # dead rows sit at 0
            pages.append(sum(-(-(int(n) + 1) // eng.pool.block_size)
                             for n in pos if n > 0))
            return decode(*args)

        eng._decode = recording_decode
        obs.enable(annotate_device=False)
        done, stats = eng.run(reqs)
        assert all(c.ok for c in done) and stats.preemptions == 0
        counters = obs.get_metrics().snapshot()["counters"]
        assert len(pages) == stats.decode_steps
        assert counters["engine.decode_kv_pages"] == sum(pages)
        # prompts of 12-28 tokens in blocks of 8: most live rows span pages
        assert counters["engine.decode_kv_pages"] > \
            counters["engine.decode_rows_live"]
