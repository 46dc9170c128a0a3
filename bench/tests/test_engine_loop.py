"""The readers of the engine loop's spans and counters, on hand-built
inputs whose values are worked out below, and on runs of the program on the
CPU: its spans pair one-to-one with the profiler's events, and a traced
tiny chat run gives the span- and counter-based readers something to read."""
import jax
import numpy as np
import pytest

from bench import run as R
from bench import trace_reduce as tr
from bench.run import load_reader
from bench.tests import tiny
from repro import obs
from repro.obs.trace import Tracer

CELL = "chat.internlm2-1.8b"


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _loop_trace():
    """One second: an admission, two decode steps, then a wait.  Device
    ops leave idle 0-10 and 15-30 ms, 280-310, 580-670 and 930-1000."""
    spans = [("schedule", 0.00, 0.02), ("admit", 0.02, 0.30),
             ("prefill", 0.03, 0.05),
             ("decode_step", 0.30, 0.60), ("bookkeep", 0.60, 0.64),
             ("schedule", 0.64, 0.66),
             ("decode_step", 0.66, 0.95), ("bookkeep", 0.95, 0.97),
             ("idle_wait", 0.97, 1.00)]
    ops = [tr.Op(n, None, s, e) for n, s, e in
           [("fusion.1", 0.010, 0.015), ("while.1", 0.03, 0.28),
            ("while.2", 0.31, 0.58), ("while.2", 0.67, 0.93)]]
    return tr.Trace({"/device:TPU:0": ops}, {}, spans, (0.0, 1.0))


def test_host_gap_by_hand(capsys):
    read = load_reader("host_gap_ms.serve")
    # idle in schedule 10 + 5 + 20 ms, decode_step 10 + 20 + 10 + 20,
    # bookkeep 40 + 20: 155 ms over 2 steps (admit's 30 and idle_wait's
    # 30 are not the host's share of a step)
    assert read(None, {"trace": _loop_trace()}) == pytest.approx(77.5)
    err = capsys.readouterr().err
    assert "admit 15.000" in err and "(no loop span) 0.000" in err


def test_host_gap_none_without_loop_spans_or_device():
    read = load_reader("host_gap_ms.serve")
    t = _loop_trace()
    older = [s for s in t.spans if s[0] not in ("schedule", "bookkeep")]
    parent_like = tr.Trace(t.ops, {}, older, t.window)
    assert read(None, {"trace": parent_like}) is None
    assert read(None, {"trace": tr.Trace({}, {}, t.spans, t.window)}) is None


def _tracer_with(events, dropped=0):
    t = Tracer(annotate_device=False)
    for name, ts, dur, args in events:
        t._append({"name": name, "ph": "X", "ts": ts, "dur": dur,
                   "args": args})
    t.dropped = dropped
    return t


# (name, ts us, dur us, args)
ADMISSIONS = [("decode_step", 0, 100, {"live": 2}),
              ("admit", 110, 40, {"rid": 1}), ("admit", 160, 60, {"rid": 2}),
              ("decode_step", 230, 100, {"live": 4}),
              ("admit", 340, 50, {"rid": 3}),
              ("decode_step", 400, 100, {"live": 1})]


def test_itl_prefill_by_hand(monkeypatch):
    read = load_reader("itl_prefill_ms.serve")
    monkeypatch.setattr(obs, "get_tracer", lambda: _tracer_with(ADMISSIONS))
    # step 2: the first admission stalls the 2 older rows (40 us x 2), the
    # second also the row the first admitted (60 x 3); step 3's only row
    # is the one admitted before it (50 x 0).  260 us over 2 + 4 + 1 gaps.
    assert read(None, {}) == pytest.approx(260 / 7 * 1e-3)


@pytest.mark.parametrize("events,dropped", [
    (ADMISSIONS, 1),                                   # part of the window
    ([e for e in ADMISSIONS if e[0] == "admit"], 0),   # no decode step
])
def test_itl_prefill_none(monkeypatch, events, dropped):
    read = load_reader("itl_prefill_ms.serve")
    monkeypatch.setattr(obs, "get_tracer",
                        lambda: _tracer_with(events, dropped))
    assert read(None, {}) is None


def test_prefill_pad_share_by_hand():
    read = load_reader("prefill_pad_share.serve")
    assert read(None, {}) is None                      # not counted
    obs.counter("engine.prefill_tokens").inc(300)      # 64 + 236
    obs.counter("engine.prefill_bucket_tokens").inc(512)   # 256 + 256
    assert read(None, {}) == pytest.approx(100 * (1 - 300 / 512))


def test_decode_row_share_by_hand():
    read = load_reader("decode_row_share.serve")
    out = {"info": {"slots": 8}}
    obs.counter("engine.decode_steps").inc(10)
    assert read(None, out) is None                     # rows not counted
    obs.counter("engine.decode_rows_live").inc(40)
    assert read(None, out) == pytest.approx(50.0)


def _profiler_spans(trace_dir):
    """Host events of the trace that carry a `span_id` stat, by id."""
    pd = jax.profiler.ProfileData.from_file(tr.find_xplane(str(trace_dir)))
    out = {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                stats = tr._stats(e)
                if "span_id" in stats:
                    out[int(stats["span_id"])] = (e.name, e.start_ns, stats)
    return out


def test_tracer_spans_pair_with_profiler_events(tmp_path):
    from repro.configs.registry import get_smoke_config
    from repro.models import init_lm
    from repro.serving.engine import Engine, synthetic_requests
    cfg = get_smoke_config("internlm2-1.8b")
    eng = Engine(init_lm(jax.random.PRNGKey(0), cfg), cfg, max_batch=2,
                 max_prompt=16, max_new=4, prefix_cache=True)
    reqs = synthetic_requests(3, pattern="burst", min_prompt=4, max_prompt=16,
                              min_new=2, max_new=4, vocab=cfg.vocab_size,
                              seed=1)
    eng.run(reqs)                                       # compile first
    obs.enable(annotate_device=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.run(reqs)
    obs.disable()
    mine = {e["args"]["span_id"]: e for e in obs.get_tracer().events()
            if e["ph"] == "X"}
    prof = _profiler_spans(tmp_path)
    assert len(mine) > 10 and set(prof) == set(mine)
    offsets = []
    for sid, ev in mine.items():
        name, start_ns, stats = prof[sid]
        assert name == ev["name"]                       # the bare name
        for k in ("rid", "step", "bucket", "live"):
            if k in ev["args"]:
                assert int(stats[k]) == ev["args"][k]
        offsets.append(start_ns * 1e-3 - ev["ts"])      # us
    assert np.ptp(offsets) < 1e3


def test_traced_tiny_chat_run_reads_the_loop():
    args = R.parse(["--workload", CELL, "--seed", "2900000001",
                    "--seconds", "2", "--trace", "1"])
    res = R.run(args, require_chip=False, config_override=tiny.serve_config,
                mix_override=tiny.serve_mix)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 <= m["itl_prefill_ms.serve"]
    assert 0 <= m["prefill_pad_share.serve"] < 100
    assert 0 < m["decode_row_share.serve"] <= 100
    assert "queue_wait_p95_ms.serve" in m
    # the CPU trace has no device plane: nothing for the device readers
    assert "host_gap_ms.serve" not in m
