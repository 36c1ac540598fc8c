"""CI bench regression gate: fail when serving perf or recall regresses.

Compares a freshly generated ``BENCH_serve.json`` (``benchmarks.run --fast
--json``) against the committed baseline and exits non-zero when, on the
gated row (batch-256 ivfpq, f32 LUT by default):

* QPS drops by more than ``--max-qps-drop`` (fractional, default 0.20), or
* recall@10 drops by more than ``--max-recall-drop`` (absolute, 0.02).

Once ``bench_stream`` rows are present, the streaming scenario is gated
too: update throughput (``upserts_per_sec``, fractional drop limit
``--max-ups-drop``, default 0.25) and the streaming recall@10 (same
absolute limit as the serving row). The ``durability`` section gates the
WAL write-path overhead within the fresh file (WAL-on upsert throughput
no more than ``--max-wal-overhead`` below WAL-off, default 0.25).

Two scan-path gates run within the fresh file (same machine, same run, so
no baseline needed): the quantized-LUT rows must hold ``qps >=
--min-lut-qps-ratio`` (default 0.95) of the f32 row, and the batch-64
fused-vs-staged speedup must stay >= ``--min-b64-speedup`` (default 1.0 —
the compact small-batch scan and re-rank pre-filter exist to keep it
there).

The ``observability`` section gates the tracing overhead within the
fresh file: an attached-but-inert tracer must cost <=
``--max-trace-off-overhead`` (default 1%) of batch-256 ivfpq p50, and
end-to-end histogram recording <= ``--max-hist-overhead`` (default 3%).

The ``zoo`` section gates the reducer/index-zoo recall pairs within the
fresh file: OPQ must hold recall@10 vs plain PQ at equal code bytes, and
the qpad (MPAD) reducer vs PCA at equal output dim — each within
``--zoo-recall-tol`` (default 0.005) of top-k tie noise.

A missing gated row in the FRESH file is itself a failure (the bench
silently lost coverage); a missing row in the BASELINE only warns, so the
gate can be introduced onto older baselines without a flag day.

The QPS compare is machine-absolute: refresh the committed baseline from a
CI artifact when runner hardware shifts, or widen ``--max-qps-drop`` if the
fleet is heterogeneous (recall@10 is hardware-independent either way).

  python benchmarks/check_regression.py BASELINE.json FRESH.json
"""
from __future__ import annotations

import argparse
import json
import sys

GATED = dict(index="ivfpq", lut_dtype="f32", batch=256)
STREAM_GATED = dict(scenario="stream_90_10", index="ivfpq")
# zoo recall pairs (challenger, reference): the challenger must hold
# recall@10 within --zoo-recall-tol of the reference, same file/run
ZOO_PAIRS = (
    ("opq-vs-pq@8B", "opq8x256", "pq8x256"),
    ("qpad-vs-pca@32d", "qpad32>flat", "pca32>flat"),
)


def find_row(doc: dict, key: str = "rows", **sel):
    for row in doc.get(key, []):
        if all(row.get(k) == v for k, v in sel.items()):
            return row
    return None


def check_stream(baseline: dict, fresh: dict, max_ups_drop: float = 0.25,
                 max_recall_drop: float = 0.02):
    """Gate the streaming scenario: update throughput + streaming recall.

    Active only once ``bench_stream`` rows exist: a baseline without a
    ``stream`` section skips the compare (pre-streaming baselines); a
    FRESH file without one while the baseline has it is a failure (the
    bench lost coverage).
    """
    failures, report = [], []
    base = find_row(baseline, key="stream", **STREAM_GATED)
    new = find_row(fresh, key="stream", **STREAM_GATED)
    sel = " ".join(f"{k}={v}" for k, v in STREAM_GATED.items())
    if base is None:
        report.append(f"baseline has no stream row ({sel}); skipping "
                      "stream compare")
        return failures, report
    if new is None:
        failures.append(f"fresh bench is missing the stream row ({sel})")
        return failures, report
    ups_drop = (1.0 - new["upserts_per_sec"] / base["upserts_per_sec"]
                if base["upserts_per_sec"] else 0.0)
    rec_drop = base["recall_at_10"] - new["recall_at_10"]
    report.append(f"upserts/s : {base['upserts_per_sec']} -> "
                  f"{new['upserts_per_sec']} (drop {ups_drop:+.1%}, "
                  f"limit {max_ups_drop:.0%})")
    report.append(f"stream rec: {base['recall_at_10']:.4f} -> "
                  f"{new['recall_at_10']:.4f} (drop {rec_drop:+.4f}, "
                  f"limit {max_recall_drop})")
    if ups_drop > max_ups_drop:
        failures.append(
            f"update-throughput regression on {sel}: "
            f"{base['upserts_per_sec']} -> {new['upserts_per_sec']} "
            f"({ups_drop:.1%} > {max_ups_drop:.0%})")
    if rec_drop > max_recall_drop:
        failures.append(
            f"streaming recall@10 regression on {sel}: "
            f"{base['recall_at_10']:.4f} -> {new['recall_at_10']:.4f} "
            f"(drop {rec_drop:.4f} > {max_recall_drop})")
    return failures, report


def check_durability(baseline: dict, fresh: dict,
                     max_wal_overhead: float = 0.25,
                     min_gc_speedup: float = 2.0,
                     max_inc_frac: float = 0.10):
    """Gate the durability/replication operations numbers.

    Unlike the throughput gates these are *within-file*: the fresh bench
    measures each pair on the same machine in the same run, so the ratios
    are hardware-independent and need no baseline:

    * WAL write-path overhead (``wal_overhead_frac`` <=
      ``--max-wal-overhead``),
    * group commit: the 8-thread fsync=always burst must run >=
      ``--min-group-commit-speedup`` faster grouped than ungrouped (the
      coalesced fsyncs are the whole point),
    * incremental snapshots: the delta-only link's bytes must stay <=
      ``--max-inc-snapshot-frac`` of the full checkpoint (delta-sized,
      not base-sized).

    A baseline without a ``durability`` section (or without the newer
    subsections) only means the gate predates it; a FRESH file missing
    something the baseline has is lost coverage.
    """
    failures, report = [], []
    new = fresh.get("durability")
    if new is None:
        if baseline.get("durability") is not None:
            failures.append("fresh bench is missing the durability section")
        else:
            report.append("no durability section; skipping WAL-overhead gate")
        return failures, report
    frac = new["wal_overhead_frac"]
    report.append(f"wal ovhd  : {new['upserts_per_sec_wal_off']} -> "
                  f"{new['upserts_per_sec_wal_on']} ups/s with WAL on "
                  f"({frac:+.1%}, limit {max_wal_overhead:.0%})")
    report.append(f"recovery  : {new['recovery_rows']} rows in "
                  f"{new['recovery_seconds']}s "
                  f"({new['recovery_rows_per_sec']} rows/s)")
    if frac > max_wal_overhead:
        failures.append(
            f"WAL write-path overhead too high: "
            f"{new['upserts_per_sec_wal_off']} -> "
            f"{new['upserts_per_sec_wal_on']} ups/s "
            f"({frac:.1%} > {max_wal_overhead:.0%})")
    base_dur = baseline.get("durability") or {}
    gc = new.get("group_commit")
    if gc is None:
        if base_dur.get("group_commit") is not None:
            failures.append("fresh bench is missing durability.group_commit")
    else:
        report.append(
            f"grp commit: {gc['appends_per_sec_ungrouped']} -> "
            f"{gc['appends_per_sec_grouped']} appends/s "
            f"({gc['speedup']:.2f}x, floor {min_gc_speedup}x; "
            f"fsyncs {gc['fsyncs_grouped']}/{gc['fsyncs_ungrouped']})")
        if gc["speedup"] < min_gc_speedup:
            failures.append(
                f"group-commit speedup too low: {gc['speedup']:.2f}x < "
                f"{min_gc_speedup}x on the fsync=always burst")
    inc = new.get("incremental_snapshot")
    if inc is None:
        if base_dur.get("incremental_snapshot") is not None:
            failures.append(
                "fresh bench is missing durability.incremental_snapshot")
    else:
        report.append(
            f"inc snap  : {inc['incremental_bytes']} of "
            f"{inc['full_bytes']} bytes "
            f"({inc['bytes_frac']:.1%}, limit {max_inc_frac:.0%}; "
            f"base_rows={inc['base_rows']} delta_rows={inc['delta_rows']})")
        if inc["bytes_frac"] > max_inc_frac:
            failures.append(
                f"incremental snapshot too large: "
                f"{inc['incremental_bytes']} bytes is "
                f"{inc['bytes_frac']:.1%} of the {inc['full_bytes']}-byte "
                f"full checkpoint (> {max_inc_frac:.0%} — the delta-only "
                "link is scaling with base rows)")
    return failures, report


def check_observability(baseline: dict, fresh: dict,
                        max_trace_off: float = 0.01,
                        max_hist: float = 0.03):
    """Gate the tracing overhead — within the fresh file.

    The three postures (no tracer / tracer attached but inert /
    histograms recording) run interleaved on the same ivfpq engine, so
    the paired median ratios are hardware-independent:

    * an inert tracer must cost <= ``--max-trace-off-overhead`` of p50
      (default 1% — the serve path takes no timestamp when every
      instrument is off),
    * end-to-end histogram recording must cost <= ``--max-hist-overhead``
      (default 3% — a block + bisect per search, nothing device-side).
    """
    failures, report = [], []
    new = fresh.get("observability")
    if new is None:
        if baseline.get("observability") is not None:
            failures.append(
                "fresh bench is missing the observability section")
        else:
            report.append("no observability section; skipping tracing-"
                          "overhead gate")
        return failures, report
    report.append(
        f"trace ovhd: inert {new['trace_off_overhead']:+.2%} "
        f"(limit {max_trace_off:.0%}), histograms "
        f"{new['hist_overhead']:+.2%} (limit {max_hist:.0%}) on "
        f"base p50 {new['p50_us_base']}us")
    if new["trace_off_overhead"] > max_trace_off:
        failures.append(
            f"inert-tracer overhead too high: "
            f"{new['trace_off_overhead']:.2%} > {max_trace_off:.0%} "
            f"({new['p50_us_base']}us -> {new['p50_us_traced_off']}us "
            "p50 with an all-off tracer attached)")
    if new["hist_overhead"] > max_hist:
        failures.append(
            f"histogram-recording overhead too high: "
            f"{new['hist_overhead']:.2%} > {max_hist:.0%} "
            f"({new['p50_us_base']}us -> {new['p50_us_hist_on']}us "
            "p50 with e2e histograms on)")
    return failures, report


def check_zoo(baseline: dict, fresh: dict, recall_tol: float = 0.005):
    """Gate the reducer/index-zoo recall pairs — within the fresh file.

    Both rows of each pair run on the same corpus in the same process, so
    the compare is hardware-independent and needs no baseline:

    * **opq vs pq at equal code bytes** — the learned rotation's whole
      point is better codes for the same budget; its fit keeps the best
      reconstruction among iterates *including* the un-rotated one, so
      falling below plain PQ's recall (beyond ``--zoo-recall-tol`` of
      top-k tie noise) means the rotation path is broken;
    * **qpad vs pca at equal output dim** — the paper's claim: the
      quantile-preserving projection beats variance-preserving PCA for
      neighbor retrieval at the same dimension budget.

    A baseline without a ``zoo`` section predates the zoo bench and only
    warns; a FRESH file missing it (or missing a pair row) is lost
    coverage and fails.
    """
    failures, report = [], []
    new = fresh.get("zoo")
    if new is None:
        if baseline.get("zoo") is not None:
            failures.append("fresh bench is missing the zoo section")
        else:
            report.append("no zoo section; skipping reducer/index-zoo gates")
        return failures, report
    for name, challenger, reference in ZOO_PAIRS:
        c = find_row(fresh, key="zoo", spec=challenger)
        r = find_row(fresh, key="zoo", spec=reference)
        missing = [s for s, row in ((challenger, c), (reference, r))
                   if row is None]
        if missing:
            failures.append(f"fresh bench is missing zoo row(s) "
                            f"{missing} ({name} gate)")
            continue
        gain = c["recall_at_10"] - r["recall_at_10"]
        report.append(f"zoo {name}: {challenger} {c['recall_at_10']:.4f} "
                      f"vs {reference} {r['recall_at_10']:.4f} "
                      f"(gain {gain:+.4f}, floor -{recall_tol})")
        if gain < -recall_tol:
            failures.append(
                f"zoo recall regression ({name}): {challenger} "
                f"recall@10 {c['recall_at_10']:.4f} fell "
                f"{-gain:.4f} below {reference} "
                f"{r['recall_at_10']:.4f} (> {recall_tol} tolerance)")
    return failures, report


def check_lut_parity(fresh: dict, min_ratio: float = 0.95):
    """Gate quantized-LUT throughput against f32 — within the fresh file.

    The narrow LUTs (bf16/int8) exist to make the ADC scan cheaper; a
    regression where they fall behind the f32 path (as the pre-uint8
    dequantize-then-gather refs did) defeats their purpose, so each
    quantized batch-256 ivfpq row must hold ``qps >= min_ratio * f32
    qps``. Same-machine, same-run rows: the ratio is hardware-independent
    and needs no baseline.
    """
    failures, report = [], []
    f32 = find_row(fresh, index="ivfpq", lut_dtype="f32", batch=256)
    if f32 is None:
        failures.append("fresh bench is missing the ivfpq f32 batch-256 "
                        "row (lut-parity gate)")
        return failures, report
    for lut in ("bf16", "int8"):
        row = find_row(fresh, index="ivfpq", lut_dtype=lut, batch=256)
        if row is None:
            failures.append(f"fresh bench is missing the ivfpq {lut} "
                            "batch-256 row (lut-parity gate)")
            continue
        ratio = row["qps"] / f32["qps"] if f32["qps"] else 1.0
        report.append(f"lut {lut:4s}: {row['qps']} qps vs f32 "
                      f"{f32['qps']} ({ratio:.2f}x, floor {min_ratio})")
        if ratio < min_ratio:
            failures.append(
                f"quantized-LUT slowdown: ivfpq {lut} runs {row['qps']} "
                f"qps vs f32 {f32['qps']} ({ratio:.2f}x < {min_ratio}x)")
    return failures, report


def check_small_batch(baseline: dict, fresh: dict,
                      min_b64_speedup: float = 1.0):
    """Gate the small-batch scan path — within the fresh file.

    The batch-64 fused-vs-staged speedup must stay >= ``min_b64_speedup``
    (the nprobe-proportional compact scan + re-rank pre-filter exist to
    fix the small-batch regression, so losing them must fail CI). The
    ``batch_sweep`` section is lost-coverage-checked against the baseline
    like the other sections.
    """
    failures, report = [], []
    if baseline.get("batch_sweep") and not fresh.get("batch_sweep"):
        failures.append("fresh bench is missing the batch_sweep section")
    row = find_row(fresh, key="staged_vs_fused", index="ivfpq", batch=64)
    if row is None:
        failures.append("fresh bench is missing the batch-64 "
                        "staged_vs_fused row (small-batch gate)")
        return failures, report
    report.append(f"b64 fused : {row['speedup']:.2f}x vs staged "
                  f"(floor {min_b64_speedup}x)")
    if row["speedup"] < min_b64_speedup:
        failures.append(
            f"small-batch regression: batch-64 fused-vs-staged speedup "
            f"{row['speedup']:.2f}x < {min_b64_speedup}x")
    return failures, report


def check(baseline: dict, fresh: dict, max_qps_drop: float = 0.20,
          max_recall_drop: float = 0.02, max_ups_drop: float = 0.25,
          max_wal_overhead: float = 0.25, min_lut_ratio: float = 0.95,
          min_b64_speedup: float = 1.0, min_gc_speedup: float = 2.0,
          max_inc_frac: float = 0.10, max_trace_off: float = 0.01,
          max_hist: float = 0.03, zoo_recall_tol: float = 0.005):
    """Returns (failures, report_lines); empty failures == gate passes."""
    failures, report = [], []
    zf, zr = check_zoo(baseline, fresh, zoo_recall_tol)
    failures += zf
    report += zr
    sf, sr = check_stream(baseline, fresh, max_ups_drop, max_recall_drop)
    failures += sf
    report += sr
    df, dr = check_durability(baseline, fresh, max_wal_overhead,
                              min_gc_speedup, max_inc_frac)
    failures += df
    report += dr
    of, orp = check_observability(baseline, fresh, max_trace_off, max_hist)
    failures += of
    report += orp
    lf, lr = check_lut_parity(fresh, min_lut_ratio)
    failures += lf
    report += lr
    bf, br = check_small_batch(baseline, fresh, min_b64_speedup)
    failures += bf
    report += br
    base = find_row(baseline, **GATED)
    new = find_row(fresh, **GATED)
    sel = " ".join(f"{k}={v}" for k, v in GATED.items())
    if new is None:
        failures.append(f"fresh bench is missing the gated row ({sel})")
        return failures, report
    if base is None:
        report.append(f"baseline has no gated row ({sel}); skipping compare")
        return failures, report
    qps_drop = 1.0 - new["qps"] / base["qps"] if base["qps"] else 0.0
    rec_drop = base["recall_at_10"] - new["recall_at_10"]
    report.append(f"qps    : {base['qps']} -> {new['qps']} "
                  f"(drop {qps_drop:+.1%}, limit {max_qps_drop:.0%})")
    report.append(f"recall : {base['recall_at_10']:.4f} -> "
                  f"{new['recall_at_10']:.4f} (drop {rec_drop:+.4f}, "
                  f"limit {max_recall_drop})")
    if qps_drop > max_qps_drop:
        failures.append(
            f"QPS regression on {sel}: {base['qps']} -> {new['qps']} "
            f"({qps_drop:.1%} > {max_qps_drop:.0%})")
    if rec_drop > max_recall_drop:
        failures.append(
            f"recall@10 regression on {sel}: {base['recall_at_10']:.4f} -> "
            f"{new['recall_at_10']:.4f} (drop {rec_drop:.4f} > "
            f"{max_recall_drop})")
    return failures, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("baseline", help="committed BENCH_serve.json")
    ap.add_argument("fresh", help="freshly generated BENCH_serve.json")
    ap.add_argument("--max-qps-drop", type=float, default=0.20,
                    help="max fractional QPS drop (default 0.20)")
    ap.add_argument("--max-recall-drop", type=float, default=0.02,
                    help="max absolute recall@10 drop (default 0.02)")
    ap.add_argument("--max-ups-drop", type=float, default=0.25,
                    help="max fractional update-throughput drop on the "
                         "streaming scenario (default 0.25)")
    ap.add_argument("--max-wal-overhead", type=float, default=0.25,
                    help="max fractional upsert-throughput cost of the WAL "
                         "(WAL-on vs WAL-off, within the fresh file; "
                         "default 0.25)")
    ap.add_argument("--min-lut-qps-ratio", type=float, default=0.95,
                    help="min bf16/int8 QPS as a fraction of the f32 row "
                         "(within the fresh file; default 0.95)")
    ap.add_argument("--min-b64-speedup", type=float, default=1.0,
                    help="min batch-64 fused-vs-staged speedup (within the "
                         "fresh file; default 1.0)")
    ap.add_argument("--min-group-commit-speedup", type=float, default=2.0,
                    help="min grouped-vs-ungrouped speedup on the 8-thread "
                         "fsync=always burst (within the fresh file; "
                         "default 2.0)")
    ap.add_argument("--max-inc-snapshot-frac", type=float, default=0.10,
                    help="max incremental-snapshot bytes as a fraction of "
                         "the full checkpoint (within the fresh file; "
                         "default 0.10)")
    ap.add_argument("--max-trace-off-overhead", type=float, default=0.01,
                    help="max fractional p50 cost of an attached-but-inert "
                         "tracer (within the fresh file; default 0.01)")
    ap.add_argument("--max-hist-overhead", type=float, default=0.03,
                    help="max fractional p50 cost of e2e latency-histogram "
                         "recording (within the fresh file; default 0.03)")
    ap.add_argument("--zoo-recall-tol", type=float, default=0.005,
                    help="absolute recall@10 slack on the zoo pairs (opq "
                         "vs pq at equal bytes, qpad vs pca at equal dim; "
                         "within the fresh file; default 0.005)")
    args = ap.parse_args(argv)
    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.fresh) as f:
        fresh = json.load(f)
    failures, report = check(baseline, fresh, args.max_qps_drop,
                             args.max_recall_drop, args.max_ups_drop,
                             args.max_wal_overhead, args.min_lut_qps_ratio,
                             args.min_b64_speedup,
                             args.min_group_commit_speedup,
                             args.max_inc_snapshot_frac,
                             args.max_trace_off_overhead,
                             args.max_hist_overhead,
                             args.zoo_recall_tol)
    for line in report:
        print(line)
    if failures:
        for msg in failures:
            print(f"REGRESSION: {msg}", file=sys.stderr)
        return 1
    print("bench regression gate: OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
