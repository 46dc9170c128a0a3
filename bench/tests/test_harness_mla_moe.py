"""The MLA + MoE share's cell on the CPU at a tiny size: the engine's
logits through the latent block pool against the plain reference, whole
runs of the harness (sound: correct; the fp8 control and a planted fault:
not), and the per-layer readers on counted numbers."""
import dataclasses
import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench import faults, run as R, serve_mla_moe as D, work_mla_moe
from bench.reference import mm
from bench.reference_mla_moe import Reference
from bench.tests import tiny_mla_moe as T

LIMIT = 0.03        # tiny size: program 0.0003-0.0099 over seeds 1-4, fp8 0.11


def _tiny_config():
    spec = R.load_spec()
    _, conf, _, _ = R.cell_entries(spec, T.CELL)
    with open(R.ROOT / conf["file"]) as f:
        return T.config(json.load(f))


def test_engine_logits_match_the_reference():
    """float32 throughout: prefill, then decode through the latent block
    pool and the paged MLA kernel, on an expert share of 4 of 16 experts;
    every logit the engine samples from equals the plain reference's."""
    import jax
    import jax.numpy as jnp
    from repro.serving.engine import Engine
    from repro.serving.engine.request import Request
    c = _tiny_config()
    pcfg = D.program_config(c, dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          D.make_params(pcfg, 11))
    eng = Engine(params, pcfg, max_batch=4, max_prompt=64, max_new=8,
                 prefix_cache=True, use_paged_kernel=True)
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, tokens=rng.randint(0, c["vocab_size"], n).astype(
        np.int32), max_new_tokens=6) for i, n in enumerate([7, 20, 33])]
    got = []                    # the logits each token was sampled from
    decode, prefill = eng._decode, eng._prefills[16]

    def rec_decode(params, tok, caches, pos, tables):
        logits, caches = decode(params, tok, caches, pos, tables)
        got.append(np.asarray(logits[int(np.argmax(np.asarray(pos)))]))
        return logits, caches

    def rec_prefill(*args):
        logits, contig = prefill(*args)
        got.append(np.asarray(logits[0]))
        return logits, contig
    eng._decode = rec_decode
    eng._prefills = {b: rec_prefill for b in eng._prefills}
    done = []
    for r in reqs:              # one live row at a time: its logits are known
        got.clear()
        d, _ = eng.run([r])
        done.append((d[0], list(got)))
    ref = Reference(c, params)
    for r, (d, logits) in zip(reqs, done):
        assert d.ok and len(logits) == len(d.tokens)
        seq = np.concatenate([r.tokens, np.asarray(d.tokens[:-1], np.int32)])
        x = ref.hidden(jnp.asarray(np.pad(seq, (0, 64 - len(seq)))))
        want = np.asarray(mm(x, params["lm_head"]))[:, :c["vocab_size"]]
        p = len(r.tokens)
        for j, lg in enumerate(logits):
            np.testing.assert_allclose(lg[:c["vocab_size"]], want[p - 1 + j],
                                       atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("seed", [1, 3])
def test_sound_run_is_correct(seed):
    res = T.run(seed=seed, config_override=lambda c: T.config(c, LIMIT))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 3 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_mean_ms", "itl_mean_ms",
                                   "output_tok_s", "setup_s"}
    assert res["_info"]["compiles_in_window"] == 0
    counts = res["_info"]["info"]["moe_counts"]
    assert 0 < counts["moe.routed_held"] < counts["moe.routed_total"]


def test_fp8_control_is_not_correct():
    res = T.run(seed=3, control="fp8",
                config_override=lambda c: T.config(c, LIMIT))
    assert not res["correct"], res["checks"]
    assert res["_info"]["program"]["mean_logit_gap"]["value"] < LIMIT


def test_stale_decode_is_caught():
    res = T.run(seed=3, tamper=faults.SERVE["stale_decode"],
                config_override=lambda c: T.config(c, LIMIT))
    assert not res["correct"], res["checks"]


def test_readers_on_counted_numbers(monkeypatch):
    """The counter readers divide what the program counted as stated; on a
    program without the counters they read nothing."""
    from repro import obs
    c = json.load(open(R.ROOT / "bench/configs/deepseek-v3-671b.ep32.json"))
    ctx = SimpleNamespace(config=c, peak={"bf16_flops": 197e12,
                                          "hbm_bytes_s": 819e9},
                          devices=[None])
    rows = R.load_reader("moe_rows_per_expert.serve")
    mfu = R.load_reader("mfu.serve_mla_moe")
    obs.reset()
    obs.enable(annotate_device=False)
    try:
        assert rows(ctx, {}) is None
        obs.counter("engine.decode_steps").inc(10)
        obs.counter("moe.routed_held").inc(10 * 4 * 8 * 3)
        assert rows(ctx, {}) == pytest.approx(3.0)
        obs.counter("moe.prefill_routed_held").inc(0)
        req = SimpleNamespace(arrival_s=0.0)
        comp = SimpleNamespace(ok=True, prompt_len=100, cached_tokens=0,
                               tokens=[1, 2], done_s=1.0)
        out = {"window": SimpleNamespace(completions=[comp], requests=[req])}
        want = (work_mla_moe.served_flops(c, 100, 0, 2)
                + work_mla_moe.routed_flops(c, 960)) / 197e12 * 100
        assert mfu(ctx, out) == pytest.approx(want)
    finally:
        obs.disable()
        obs.reset()


def test_work_counts():
    c = json.load(open(R.ROOT / "bench/configs/deepseek-v3-671b.ep32.json"))
    flops, nbytes = work_mla_moe.paged_mla_decode([17, 0, 1], c)
    assert flops == 2 * 128 * (512 + 64 + 512) * 18
    assert nbytes == 2 * (3 * 16 * 576 + 2 * 128 * (576 + 512))
    f, b = work_mla_moe.moe_gmm(24, 7168, 2048, 8)
    assert f == 2 * 24 * 7168 * 2048
    assert b == 2 * (8 * 7168 * 2048 + 24 * (7168 + 2048))
    # a decode-sized layer is bound by the weights of the experts that got
    # a row: 3 rows over 2 experts read 2 experts' three matrices
    peak = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    least = work_mla_moe.moe_gmm_layer(3.0, 2.0, c, peak)
    assert least == pytest.approx(
        2 * (3 * 2 * 7168 * 2048 + 3 * 3 * (7168 + 2048)) / 819e9)
    pcfg = D.program_config(c)
    assert dataclasses.asdict(pcfg)["num_experts"] == 256
    assert pcfg.experts_held == 8 and pcfg.num_layers == 7
    assert pcfg.head_dim == 192 and pcfg.norm_eps == 1e-6
