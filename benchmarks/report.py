"""Render EXPERIMENTS.md from the dry-run / perf / serving JSONL artifacts.

    PYTHONPATH=src python -m benchmarks.report \
        --dryrun dryrun_results.jsonl --perf perf_qwen.jsonl perf_whisper.jsonl \
        perf_deepseek.jsonl --serve serve_engine.jsonl --out EXPERIMENTS.md
"""
import argparse
import json
import os
from collections import defaultdict

HW_NOTE = (
    "All numbers are per-chip, derived from compiled (post-SPMD) HLO of the "
    "512-host-device dry-run via `repro.core.hlo_analysis` (trip-count-aware; "
    "raw `cost_analysis()` counts scan bodies once and is recorded in the "
    "JSONL for reference).  Hardware constants: TPU v5e, 197 TFLOP/s bf16, "
    "819 GB/s HBM, 150 GB/s ICI budget/chip.  CPU-backend caveat: XLA:CPU "
    "legalizes bf16 via f32 converts, inflating byte counts ~1.5-2x vs a TPU "
    "build; relative (before/after) comparisons are unaffected."
)


def _load(path):
    rows = []
    with open(path) as f:
        for line in f:
            rows.append(json.loads(line))
    return rows


def _fmt(x, nd=3):
    return "n/a" if x is None else f"{x:.{nd}f}"


def dryrun_section(rows):
    out = ["## §Dry-run", "",
           "Every (architecture × input shape) cell lowered + compiled on the "
           "single-pod 16x16 (256 chip) AND multi-pod 2x16x16 (512 chip) "
           "meshes.  `skipped` cells are the documented long_500k "
           "full-attention skips (DESIGN.md §4).", ""]
    n_ok = sum(r["status"] == "ok" for r in rows)
    n_skip = sum(r["status"] == "skipped" for r in rows)
    n_err = sum(r["status"] == "error" for r in rows)
    out.append(f"**{len(rows)} cells: {n_ok} compiled OK, {n_skip} skipped, "
               f"{n_err} errors.**")
    out.append("")
    out += [
        "**Memory fit (16 GB/chip v5e).**  `memory_analysis()` per chip on "
        "the largest cells: arguments (f32 master params + int8 optimizer "
        "state + batch) = 7.5 GB (nemotron-340B) / 14.8 GB (deepseek-671B) "
        "— the int8 optimizer-state compression is what makes these fit.  "
        "Temp memory under the paper-faithful config is dominated by the "
        "remat-saved residual stack (L x s x h); enabling sequence "
        "parallelism shards it t-fold: nemotron temp 52.8 -> 21.6 GB "
        "measured, ~11 GB in TPU-native bf16 (XLA:CPU stores the scan "
        "carries in f32) -> fits.  deepseek's temp is MoE dispatch buffers "
        "(39 GB at cf=1.25 in CPU-f32; ~13 GB at bf16+cf=1.0) -> fits with "
        "the §Perf treatments.  Decode/prefill cells are far below budget.",
        ""]
    out.append("| arch | shape | mesh | status | bytes/chip GB | coll GB | "
               "compile s |")
    out.append("|---|---|---|---|---|---|---|")
    for r in rows:
        gb = (f"{r['hlo_bytes'] / 1e9:.0f}" if r.get("hlo_bytes") else "-")
        cg = (f"{r['coll_bytes'] / 1e9:.1f}" if r.get("coll_bytes") is not None
              and r["status"] == "ok" else "-")
        out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                   f"{r['status']} | {gb} | {cg} | "
                   f"{r.get('compile_s', '-')} |")
    out.append("")
    return out


def roofline_section(rows):
    out = ["## §Roofline", "", HW_NOTE, "",
           "Terms (seconds/step, per chip): compute = HLO_FLOPs/peak; "
           "memory = HLO_bytes/HBM_bw; collective = collective_bytes/ICI_bw. "
           "`useful` = MODEL_FLOPS(6·N_active·D) / HLO_FLOPs; `rf` = "
           "analytic roofline fraction (useful-FLOP throughput at the "
           "dominant-term step time vs chip peak).", ""]
    out.append("| arch | shape | compute s | memory s | collective s | "
               "dominant | useful | rf | what moves the dominant term |")
    out.append("|---|---|---|---|---|---|---|---|---|")

    def note(r):
        s2 = r.get("s2_bytes") or 0.0
        tot = r.get("hlo_bytes") or 1.0
        if r["shape"].startswith(("decode", "long")):
            return (f"decode is bandwidth-bound by construction (streams "
                    f"params+cache per token); lower bound "
                    f"{r['memory_s'] * 1e3:.1f} ms/step — batch more "
                    f"sequences to amortize")
        if r["dominant"] == "compute":
            return "at compute roofline: remat policy (dots) / larger mb"
        if r["dominant"] == "collective":
            return ("MoE dispatch + TP/FSDP traffic: EP-local combine, "
                    "fewer microbatches, bf16 reductions")
        if s2 / tot > 0.3:
            return (f"s^2 attention is {100 * s2 / tot:.0f}% of bytes: "
                    f"Pallas flash kernel (kernels/flash_attention)")
        if r["compute_s"] > 0.4 * r["memory_s"]:
            return ("within 2.5x of compute roofline: bf16 backward + "
                    "remat tuning close the gap")
        return ("residual-stream activation traffic: sequence parallelism, "
                "bf16 backward, wider per-shard GEMMs")

    for r in rows:
        if r["status"] != "ok" or r["mesh"] != "16x16":
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {_fmt(r['compute_s'])} | "
            f"{_fmt(r['memory_s'])} | {_fmt(r['collective_s'])} | "
            f"{r['dominant']} | {_fmt(r['useful_ratio'], 2)} | "
            f"{_fmt(r['roofline_fraction'], 4)} | {note(r)} |")
    out.append("")
    out += ["### Multi-pod deltas (2x16x16 vs 16x16, train_4k)", "",
            "The pod axis runs as outer data parallelism: per-chip work "
            "halves at fixed global batch; the extra cost is the cross-pod "
            "gradient all-reduce (and its share of the collective term).", ""]
    out.append("| arch | c_s 1pod | c_s 2pod | coll_s 1pod | coll_s 2pod | "
               "rf 1pod | rf 2pod |")
    out.append("|---|---|---|---|---|---|---|")
    by_key = {(r["arch"], r["shape"], r["mesh"]): r for r in rows
              if r["status"] == "ok"}
    archs = sorted({r["arch"] for r in rows})
    for a in archs:
        r1 = by_key.get((a, "train_4k", "16x16"))
        r2 = by_key.get((a, "train_4k", "2x16x16"))
        if not (r1 and r2):
            continue
        out.append(f"| {a} | {_fmt(r1['compute_s'])} | {_fmt(r2['compute_s'])} | "
                   f"{_fmt(r1['collective_s'])} | {_fmt(r2['collective_s'])} | "
                   f"{_fmt(r1['roofline_fraction'], 4)} | "
                   f"{_fmt(r2['roofline_fraction'], 4)} |")
    out.append("")
    return out


def perf_section(perf_rows_by_cell):
    out = ["## §Perf", "",
           "Hillclimb methodology: hypothesis → change → re-lower → measure "
           "(three roofline terms) → verdict.  The **paper-faithful "
           "baseline** (naive Table II attention, mb=1) and the "
           "**beyond-paper optimized** variant are reported separately.  "
           "`flash_sub` rows give the TPU-deployment memory term with the "
           "Pallas flash kernel substituted for the measured s^2 attention "
           "traffic (the XLA twin cannot keep tiles VMEM-resident; the "
           "kernel's traffic is modeled from its BlockSpecs).", ""]
    import os
    nar = os.path.join(os.path.dirname(__file__), "perf_narrative.md")
    if os.path.exists(nar):
        with open(nar) as f:
            out += [f.read(), ""]
    out += ["### Raw treatment measurements (per perf_*.jsonl)", ""]
    for cell, rows in perf_rows_by_cell.items():
        out.append(f"### {cell}")
        out.append("")
        out.append("| treatment | compute s | memory s | collective s | "
                   "dominant | rf | flash-sub mem s | flash-sub rf |")
        out.append("|---|---|---|---|---|---|---|---|")
        for r in rows:
            if r["status"] != "ok":
                out.append(f"| {r.get('tag')} | ERROR: {r.get('error', '')[:60]} |")
                continue
            out.append(
                f"| {r.get('tag')} | {_fmt(r['compute_s'])} | "
                f"{_fmt(r['memory_s'])} | {_fmt(r['collective_s'])} | "
                f"{r['dominant']} | {_fmt(r['roofline_fraction'], 4)} | "
                f"{_fmt(r.get('flash_sub_memory_s'))} | "
                f"{_fmt(r.get('flash_sub_roofline_fraction'), 4)} |")
        out.append("")
        out.append("Hypothesis log:")
        for r in rows:
            out.append(f"- **{r.get('tag')}**: {r.get('hypothesis', '')}")
        out.append("")
    return out


def train_attention_section(rows):
    """Fused-attention training sweep: step time per attn_impl on aligned vs
    unaligned shapes (`benchmarks/train_attention_sweep.py`)."""
    out = ["## §Training attention", "",
           "Full `train_step` (value_and_grad + AdamW) step times across "
           "attention impls and shape alignment.  `flash` runs the Pallas "
           "kernel pair (forward + fused custom-VJP backward); on a CPU "
           "container it executes in interpret mode, so compare the "
           "misalign ratio within an impl, not absolute times across impls "
           "(on a TPU the kernels compile).", ""]
    out.append("| impl | shape | seq | head_dim | us/step | loss | "
               "misalign ratio |")
    out.append("|---|---|---|---|---|---|---|")
    for r in rows:
        ratio = r.get("misalign_ratio")
        ratio_s = f"{ratio:.2f}x" if ratio is not None else "n/a"
        out.append(
            f"| {r['impl']} | {r['shape']} | {r['seq']} | {r['head_dim']} | "
            f"{r['us_per_step']:.0f} | {r['loss']:.3f} | {ratio_s} |")
    out.append("")
    return out


def mlp_fusion_section(rows):
    """Fused vs unfused MLP report: forward/grad times per impl on aligned
    vs 8h/3-misaligned d_ff (`benchmarks/mlp_fusion_sweep.py`)."""
    out = ["## §MLP fusion", "",
           "SwiGLU hidden (gate/up GEMM pair + silu*mul) per execution "
           "strategy of the linear-execution layer (`repro.models.linear`): "
           "`jnp` = XLA, `unfused` = two Pallas matmuls, `fused` = the "
           "single fused kernel (`kernels/fused_mlp`).  `grad` rows "
           "differentiate through each path (the fused one via its "
           "recompute-based custom-VJP backward).  CPU container: Pallas "
           "rows run in interpret mode — compare the misalign ratio within "
           "an impl and fused-vs-unfused at equal shape, not absolute "
           "times (on a TPU the kernels compile).", ""]
    out.append("| impl | d_ff | util | fwd us | grad us | fwd vs unfused | "
               "misalign ratio |")
    out.append("|---|---|---|---|---|---|---|")
    for r in rows:
        ratio = r.get("misalign_ratio")
        ratio_s = f"{ratio:.2f}x" if ratio is not None else "n/a"
        out.append(
            f"| {r['impl']} | {r['d_ff']} | {r['mxu_utilization']:.3f} | "
            f"{r['fwd_us']:.0f} | {r['grad_us']:.0f} | "
            f"{r['fwd_vs_unfused']:.2f}x | {ratio_s} |")
    out.append("")
    return out


def quant_section(rows):
    """Low-precision report: CPU-smoke kernel parity plus the tpu_v5e
    analytic dtype pricing and KV slots-per-GiB economics
    (`benchmarks/quant_sweep.py`)."""
    cpu = [r for r in rows if r["type"] in ("gemm_cpu", "mlp_cpu")]
    analytic = [r for r in rows if r["type"] == "analytic"]
    kv_slots = [r for r in rows if r["type"] == "kv_slots"]
    kv_cpu = [r for r in rows if r["type"] == "kv_cpu"]
    out = ["## §Low precision", "",
           "int8/fp8 execution (`kernels/quantized`, `linear_impl="
           "\"quantized\"`, `kv_dtype=\"int8\"`).  CPU container: kernel "
           "rows run in Pallas interpret mode, so their wall-clock proves "
           "parity, not speed — the deployment signal is the analytic "
           "dtype pricing (tpu_v5e roofline with dtype_bytes as an axis; "
           "bandwidth-only, so int8's MXU-rate bonus would only widen the "
           "win).  See docs/quantization-guide.md.", ""]
    if cpu:
        out.append("| kernel | shape | cpu us (interpret) | rel err vs f32 |")
        out.append("|---|---|---|---|")
        for r in cpu:
            shape = (f"{r['m']}x{r['k']}x{r['n']}" if "k" in r
                     else f"{r['m']}x{r['h']}x{r['f']}")
            out.append(f"| {r['impl']} | {shape} | {r['cpu_us']:.0f} | "
                       f"{r['rel_err']:.4f} |")
        out.append("")
    if analytic:
        out.append("| arch | mode | gemm | m,k,n | bound | recommended | "
                   "speedup | layers |")
        out.append("|---|---|---|---|---|---|---|---|")
        for r in analytic:
            out.append(
                f"| {r['arch']} | {r['mode']} | {r['name']} | "
                f"{r['m']},{r['k']},{r['n']} | {r['bound']} | "
                f"{r['recommended_dtype']} | {r['speedup']:.2f}x | "
                f"{r['count']} |")
        out.append("")
    if kv_cpu or kv_slots:
        out.append("KV cache at `kv_dtype=\"int8\"` (per-(token, head) f32 "
                   "scales ride alongside the int8 pool):")
        out.append("")
        for r in kv_cpu:
            out.append(f"- paged decode rel err vs f32 pool: "
                       f"{r['rel_err']:.4f} "
                       f"(pool {r['slots']}x{r['s_max']}x{r['nkv']}x{r['d']})")
        for r in kv_slots:
            out.append(
                f"- {r['arch']}: {r['slots_per_gib_auto']} -> "
                f"{r['slots_per_gib_int8']} slots/GiB at "
                f"max_seq={r['max_seq']} ({r['gain']:.2f}x)")
        out.append("")
    return out


def serve_section(rows):
    """Serving-engine latency report: aggregate tok/s is not the whole
    story — per-request TTFT and inter-token percentiles are what a serving
    SLO is written against, so they ride alongside (p50/p99)."""
    rows = [r for r in rows if "pattern" in r]
    out = ["## §Serving", "",
           "Continuous-batching engine vs static batching "
           "(`benchmarks/serve_engine.py`, CPU smoke scale; both policies "
           "share jitted programs + block pool, only the scheduler differs "
           "— see docs/serving-guide.md).  `steps` counts pool-wide decode "
           "steps: static pays for dead rows riding to each batch max.", ""]
    out.append("| pattern | policy | tok/s | TTFT p50 ms | TTFT p99 ms | "
               "ITL p50 ms | ITL p99 ms | decode steps |")
    out.append("|---|---|---|---|---|---|---|---|")
    for r in rows:
        out.append(
            f"| {r['pattern']} | {r['policy']} | {r['tok_s']:.1f} | "
            f"{r['ttft_p50_s']*1e3:.1f} | {r['ttft_p99_s']*1e3:.1f} | "
            f"{r['itl_p50_s']*1e3:.1f} | {r['itl_p99_s']*1e3:.1f} | "
            f"{r['decode_steps']} |")
    out.append("")
    by_pat = defaultdict(dict)
    for r in rows:
        by_pat[r["pattern"]][r["policy"]] = r
    gains = [(p, d["continuous"]["tok_s"] / d["static"]["tok_s"])
             for p, d in by_pat.items()
             if "continuous" in d and "static" in d and d["static"]["tok_s"]]
    if gains:
        out.append("**Continuous vs static aggregate tok/s:** "
                   + ", ".join(f"{p} {g:.2f}x" for p, g in gains) + ".")
        out.append("")
    out += failure_class_lines(rows)
    return out


def failure_class_lines(rows):
    """Failure-class breakdown next to the latency percentiles: every request
    lands in exactly one finish_reason bucket (docs/serving-guide.md,
    'Failure semantics & overload'); a healthy closed-loop run is all
    stop/length, so anything else here is signal."""
    reasons = defaultdict(int)
    preempt = resumes = 0
    for r in rows:
        for k, v in (r.get("finish_reasons") or {}).items():
            reasons[k] += v
        preempt += r.get("preemptions", 0)
        resumes += r.get("resumes", 0)
    if not reasons:
        return []
    parts = ", ".join(f"{k} {v}" for k, v in sorted(reasons.items()))
    out = [f"**Failure classes (all runs):** {parts}."]
    if preempt or resumes:
        out.append(f"**KV preemptions:** {preempt} "
                   f"({resumes} resumed exactly via the prefix cache).")
    out.append("")
    return out


def overload_section(summary):
    """Overload sweep (BENCH_overload.json): goodput + shed/timeout counts
    and p99 TTFT of completed requests as offered load scales past
    capacity — the graceful-degradation contract the CI gate enforces."""
    eng = summary.get("engine", {})
    shed = summary.get("shed_policy", {})
    out = ["### Overload (admission control under 1x/2x/4x offered load)",
           "",
           f"`benchmarks/overload_sweep.py`: {eng.get('slots', '?')} slots, "
           f"shed policy depth={shed.get('max_queue_depth')}, "
           f"TTFT SLO={shed.get('ttft_slo_steps')} steps.  Overload is shed "
           "at admission (no slot, no prefill); goodput = ok / admitted.  "
           "The CI gate requires 2x overload to complete crash-free with "
           "goodput >= 0.9.", ""]
    out.append("| offered load | ok | shed | timeout | goodput | "
               "TTFT ok p99 ms | tok/s |")
    out.append("|---|---|---|---|---|---|---|")
    for r in summary.get("loads", []):
        out.append(
            f"| {r['load']:g}x | {r['num_ok']} | {r['num_shed']} | "
            f"{r['num_timeout']} | {r['goodput']:.2f} | "
            f"{r['ttft_ok_p99_s']*1e3:.1f} | {r['tok_s']:.1f} |")
    out.append("")
    return out


def obs_section(dump_dir):
    """Observability summary (spans / step percentiles / compiles / drift /
    metrics) from an `obs.export_all` dump — `repro.obs.view` renders it;
    this section just re-titles it for EXPERIMENTS.md."""
    from repro.obs import view
    out = ["## §Observability", "",
           f"From `{dump_dir}` (written by `repro.launch.serve --obs-dump`; "
           "drift = analytic/measured-profile prediction vs span-measured "
           "step time — see docs/observability-guide.md).", ""]
    # drop render_summary's own H1 title; keep its section structure
    out += [ln.replace("## ", "### ") for ln in view.render_summary(dump_dir)
            if not ln.startswith("# ")]
    out.append("")
    return out


def analysis_section(paths):
    """Static-analysis summary from the codesign lint engine
    (`repro.analysis`): per-rule counts plus every priced shape finding, so
    EXPERIMENTS.md records which measured inefficiencies were *predicted*
    from shapes alone (docs/static-analysis-guide.md has the rule catalog)."""
    from repro.analysis import analyze
    from repro.analysis.rules import RULES

    result = analyze(paths, registry_audit=True)
    out = ["## §Static analysis", "",
           f"`python -m repro.analysis {' '.join(paths)}` over "
           f"{result.files_scanned} files + the config registry "
           "(tpu_v5e target).  Errors gate CI; warns are tracked "
           "(smoke configs and runtime-mitigated shapes are downgraded "
           "by design).", ""]
    by_rule = defaultdict(list)
    for f in result.findings:
        by_rule[f.rule_id].append(f)
    out.append("| rule | name | severity | findings |")
    out.append("|---|---|---|---|")
    for rid in sorted(by_rule):
        rule = RULES[rid]
        worst = max(by_rule[rid],
                    key=lambda f: ("info", "warn", "error").index(f.severity))
        out.append(f"| {rid} | {rule.name} | {worst.severity} | "
                   f"{len(by_rule[rid])} |")
    out.append("")
    priced = [f for f in result.findings
              if f.rule_id.startswith("SHP") and "est." in f.fix_hint]
    if priced:
        out.append("Priced shape findings (analytic GEMM model):")
        out.append("")
        for f in priced:
            out.append(f"- **{f.rule_id}** [{f.arch}] {f.fix_hint}")
        out.append("")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="dryrun_results.jsonl")
    ap.add_argument("--perf", nargs="*", default=[])
    ap.add_argument("--serve", default=None,
                    help="serve_engine.jsonl from benchmarks.serve_engine")
    ap.add_argument("--overload", default=None,
                    help="BENCH_overload.json from benchmarks.overload_sweep")
    ap.add_argument("--train-attn", default=None,
                    help="train_attention.jsonl from "
                         "benchmarks.train_attention_sweep")
    ap.add_argument("--mlp-fusion", default=None,
                    help="mlp_fusion.jsonl from benchmarks.mlp_fusion_sweep")
    ap.add_argument("--quant", default=None,
                    help="quant.jsonl from benchmarks.quant_sweep")
    ap.add_argument("--obs", default=None, metavar="DUMPDIR",
                    help="observability dump dir from obs.export_all "
                         "(e.g. `repro.launch.serve --obs-dump`); embeds the "
                         "span/compile/drift summary")
    ap.add_argument("--analysis", nargs="*", default=None, metavar="PATH",
                    help="embed the repro.analysis static-analysis summary "
                         "(default scan path: src); pass paths to override")
    ap.add_argument("--out", default="EXPERIMENTS.md")
    args = ap.parse_args()

    dry = _load(args.dryrun) if os.path.exists(args.dryrun) else []
    perf = {}
    for p in args.perf:
        cell = p.split("perf_")[-1].split(".")[0]
        perf[cell] = _load(p)

    lines = ["# EXPERIMENTS", "",
             "Generated by `python -m benchmarks.report` from "
             "dryrun_results.jsonl / perf_*.jsonl / serve_engine.jsonl "
             "(regenerate any time).", ""]
    if dry:
        lines += dryrun_section(dry)
        lines += roofline_section(dry)
    if perf:
        lines += perf_section(perf)
    if args.train_attn:
        lines += train_attention_section(_load(args.train_attn))
    if args.mlp_fusion:
        lines += mlp_fusion_section(_load(args.mlp_fusion))
    if args.quant:
        lines += quant_section(_load(args.quant))
    if args.serve:
        lines += serve_section(_load(args.serve))
    if args.overload and os.path.exists(args.overload):
        with open(args.overload) as f:
            lines += overload_section(json.load(f))
    if args.obs:
        lines += obs_section(args.obs)
    if args.analysis is not None:
        lines += analysis_section(args.analysis or ["src"])
    with open(args.out, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {args.out} ({len(lines)} lines)")


if __name__ == "__main__":
    main()
