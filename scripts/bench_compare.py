#!/usr/bin/env python
"""Bench regression tracking: diff two runs, emit a markdown delta table.

Closes the telemetry loop the round-8 ISSUE names: BENCH_r02–r05 /
MULTICHIP_r02–r05 give the repo a trajectory, but until now every round's
headline was a one-off — nothing diffed round N against N−1, so a 10% QPS
regression would ship unremarked. This tool compares any two of:

* driver round files (``BENCH_r04.json``: ``{"rc": .., "parsed": {...}}``) —
  a ``parsed: null`` round (the r05 wedge) degrades to an honest
  "no data" column, never an error;
* raw metric lines (bench.py's single-JSON-line output);
* obs metrics JSONL files (``results/metrics/*.jsonl``) — merged per process
  via obs/aggregate, then compared on timer means, counters and histogram
  percentile bounds.

Direction is inferred per metric (qps/recall/value up is good; ``*_s`` /
``*_ub`` latency down is good; config counters are informational), and the
regression threshold is configurable globally (``--threshold 0.05``) and
per metric (``--metric-threshold ivf_pq.qps=0.02``, repeatable).

Usage::

    python scripts/bench_compare.py BENCH_r04.json BENCH_r05.json
    python scripts/bench_compare.py A.json B.json --output delta.md
    python scripts/bench_compare.py old.jsonl new.jsonl --fail-on-regression

Exit 0 always (report tools must not eat a bench round), unless
``--fail-on-regression`` is set and a regression verdict exists (exit 1), or
the inputs are unreadable (exit 2).

Stdlib-only + file-path loading of obs/aggregate.py: runnable right after a
wedged round without touching the raft_tpu/jax package import lock.
"""

import argparse
import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_aggregate():
    spec = importlib.util.spec_from_file_location(
        "_obs_aggregate",
        os.path.join(_REPO, "raft_tpu", "obs", "aggregate.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_obs_aggregate"] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# loading + flattening
# ---------------------------------------------------------------------------

#: extras keys that are run CONFIG, not measurements — reported only when
#: they differ (a shape change silently explains every other delta)
_CONFIG_KEYS = {"n", "dim", "q", "k", "n_lists", "nprobe", "k_fetch",
                "itopk", "width", "scale", "tile", "chunk"}


def load_run(path):
    """(label, metric_line_or_None, note). Accepts a driver round file, a
    raw metric line, or a metrics JSONL file."""
    label = os.path.basename(path)
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        return label, None, f"unreadable: {e}"
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        # not one JSON document: try metrics JSONL via the fleet merge
        agg = _load_aggregate()
        records = agg.read_jsonl(path)
        if not records:
            return label, None, "no parseable JSON"
        return label, {"_jsonl": agg.merge_records(records)}, ""
    if isinstance(doc, dict) and "parsed" in doc:
        parsed = doc.get("parsed")
        rc = doc.get("rc")
        if not isinstance(parsed, dict):
            return label, None, (f"no data (rc={rc}, parsed=null — the "
                                 f"round died before emitting a line)")
        note = "" if rc in (0, None) else f"rc={rc}"
        return label, parsed, note
    if isinstance(doc, dict) and ("counters" in doc or "timers" in doc or
                                  "histograms" in doc):
        # a one-line metrics JSONL file parses as a single document too
        agg = _load_aggregate()
        doc["_source"] = path
        return label, {"_jsonl": agg.merge_records([doc])}, ""
    if isinstance(doc, dict):
        return label, doc, ""
    return label, None, "unrecognized JSON shape"


#: string-valued extras worth a (purely informational) row: the roofline
#: bound verdict and its peak provenance — a compute→memory flip is a
#: real signal worth seeing in the delta table, but never a "regression"
#: (round 15; numbers still carry all the gating)
_STRING_METRIC_TAILS = {"bound", "peaks_source"}


def _flatten(prefix, obj, out):
    for key, val in obj.items():
        name = f"{prefix}.{key}" if prefix else str(key)
        if isinstance(val, dict):
            _flatten(name, val, out)
        elif isinstance(val, bool):
            out[name] = 1.0 if val else 0.0
        elif isinstance(val, (int, float)):
            out[name] = float(val)
        elif isinstance(val, str) and key in _STRING_METRIC_TAILS:
            out[name] = val
    return out


def metrics_of(line):
    """Flat {metric: float} view of one loaded run."""
    if line is None:
        return {}
    if "_jsonl" in line:
        merged = line["_jsonl"]
        out = {}
        for key, val in (merged.get("counters") or {}).items():
            out[f"counters.{key}"] = float(val)
        for key, t in (merged.get("timers") or {}).items():
            out[f"timers.{key}.mean_s"] = t.get("mean_s", 0.0)
            out[f"timers.{key}.count"] = float(t.get("count", 0))
        for key, h in (merged.get("histograms") or {}).items():
            for q in ("p50_ub", "p90_ub", "p99_ub"):
                if q in h:
                    out[f"histograms.{key}.{q}"] = float(h[q])
        return out
    out = {}
    for key in ("value", "vs_baseline"):
        if isinstance(line.get(key), (int, float)):
            out[key] = float(line[key])
    extras = line.get("extras")
    if isinstance(extras, dict):
        _flatten("", extras, out)
    return out


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def direction(metric: str) -> str:
    """'up' (bigger better), 'down' (smaller better) or 'info'."""
    tail = metric.rsplit(".", 1)[-1]
    if tail in _CONFIG_KEYS or metric.startswith("counters."):
        return "info"
    # build trajectory (round 17): throughputs grow toward good — checked
    # BEFORE the `_s` suffix rule, which would read `rows_per_s` as a
    # latency; the streamed build's peak-residency predictions shrink
    # toward good (a bigger peak is a smaller margin on the 15.6M-row
    # per-chip share); the no-refine recall and the dense-vs-Hadamard
    # rotation speedup grow toward good
    if tail.endswith("rows_per_s") or tail == "rotation_speedup_x":
        return "up"
    if tail in ("build_peak_predicted_bytes",
                "sift1b_share_peak_predicted_bytes"):
        return "down"
    if tail == "no_refine_recall":
        return "up"
    if tail.endswith("_ub") or tail.endswith("_s") or "latency" in tail:
        return "down"
    # SLO plane (round 10): burn rates spend error budget — down is
    # better (this also catches availability_burn_rate, deliberately
    # before the availability rule); availability and the live recall
    # estimate/CI grow toward good; staleness, shadow drops, deadline
    # misses and unclassified verdicts shrink toward good
    if "burn" in tail:
        return "down"
    if tail == "availability":
        return "up"
    if tail in ("recall_estimate", "recall_ci_low", "recall_ci_high"):
        return "up"
    if tail in ("recall_stale", "deadline_misses", "unclassified") or \
            "dropped" in tail:
        return "down"
    # capacity/compression metrics (bench.ivf_bq.*): resident-bytes and
    # recompile counts shrink toward good; capacity rows and compression
    # ratios grow toward good — without these a 2× code-bytes regression
    # would render as informational
    if tail.endswith("bytes_per_row") or "recompiles" in tail:
        return "down"
    if tail.endswith("capacity_rows") or tail.endswith("compression_x"):
        return "up"
    # paged Pallas data plane (round 16): the packed-vs-paged throughput
    # ratio and completed compaction cycles grow toward good (ratio is
    # also caught by the qps rule below — kept explicit for the
    # zero-tolerance threshold's readability); the window's peak
    # tombstone load shrinks toward good (compaction keeping up)
    if tail in ("paged_to_packed_qps_ratio", "compaction_cycles"):
        return "up"
    if tail == "tombstone_ratio_peak":
        return "down"
    # maintenance plane (round 19): drift score and the maintained-vs-
    # control recall decay shrink toward good (the always-live index is
    # holding recall without a rebuild); completed re-clustering cycles
    # grow toward good (recall_estimate is caught by the SLO-plane rule
    # above); stale aborts are the optimistic-concurrency protocol
    # WORKING — load-dependent, informational, never a verdict
    if tail in ("drift_score", "recall_decay"):
        return "down"
    if tail == "maintenance_cycles":
        return "up"
    if tail == "stale_aborts":
        return "info"
    # capacity plane (round 18): an OOM verdict in the oversubscribed
    # chaos rung means the admission controller failed its one job —
    # shrinking toward good at zero tolerance; the measured hot-swap
    # (promote) latencies are caught by the `_s` latency rule below
    # (down), and the tier census (`tenants_resident_hot`) is a
    # configuration-dependent observation, informational by default
    if tail == "oom_verdicts":
        return "down"
    if tail in ("tenants_resident_hot", "tenants_resident_warm",
                "tenants_cold"):
        return "info"
    # flight recorder (round 19): shard-time skew and sustained-straggler
    # events shrink toward good (a hot shard is a fleet regression at any
    # ratio); windows recorded and frontier size grow toward good — more
    # timeline coverage and more Pareto-optimal operating points — but a
    # dip is a run-length artifact, not a perf regression, so their
    # verdicts stay rows, never gates
    if tail in ("shard_skew", "straggler_events"):
        return "down"
    if tail in ("flight_windows", "frontier_points"):
        return "up"
    # filtered & hybrid search (round 20): filtered recall, the fused
    # hybrid recall and the filtered-to-unfiltered throughput ratio grow
    # toward good — push-down means a filter costs VMEM masking plus plan
    # widening, never a second scan, so the ratio regressing is the
    # kernel operand path degrading (zero tolerance below); recompiles
    # during filtered search are caught by the recompile rule above
    # (down, zero tolerance)
    if tail in ("filtered_recall", "hybrid_recall",
                "filtered_to_unfiltered_qps_ratio"):
        return "up"
    # autotuning loop (round 21): the tuned operating point's throughput
    # and recall grow toward good; controller actions during the induced
    # spike, SLO-breach windows and unexplained diagnoses shrink toward
    # good (a louder controller or a diagnosis the attribution engine
    # can't classify is the loop degrading, not the workload); the
    # post-spike budget burn is caught by the `burn` rule above (down,
    # zero tolerance below) — an episode that ends with any SLO still in
    # breach means the controller failed to absorb the spike
    if tail in ("tuned_qps", "tuned_recall"):
        return "up"
    if tail in ("controller_actions", "slo_breach_windows",
                "unexplained_diagnoses", "calm_actions"):
        return "down"
    # cost-model accuracy (round 11): the predicted/measured HBM ratio is
    # best AT 1.0 — drift in either direction is the predictor degrading,
    # so the verdict compares |ratio − 1| across rounds ("one" direction);
    # an unexplained retrace (no shape-diff attribution) is a
    # zero-recompile-contract violation, shrinking toward good
    if tail.endswith("predicted_to_measured"):
        return "one"
    if tail == "unexplained_retraces":
        return "down"
    # roofline plane (round 15): utilizations and achieved throughput
    # grow toward good (model_to_measured = bound/measured ≤ 1, bigger =
    # closer to the roofline); padding fractions shrink toward good;
    # `bound` flips are handled as string info rows, never regressions
    if tail in ("mxu_utilization", "hbm_bw_utilization",
                "achieved_gflops", "model_to_measured", "tile_fill"):
        return "up"
    if tail.endswith("padded_fraction") or \
            tail.endswith("padded_row_fraction") or \
            tail.endswith("padded_strip_fraction"):
        return "down"
    if "qps" in tail or tail in ("value", "vs_baseline", "recall",
                                 "recall_gate_met", "ann_beats_brute",
                                 "per_chip_measured", "per_chip_recall"):
        return "up"
    return "info"


#: per-metric defaults (overridable via --metric-threshold): the ivf_bq
#: capacity/compression numbers are step functions of the configuration —
#: ANY shrink is a regression worth a row, so their threshold is 0
_DEFAULT_METRIC_THRESHOLDS = {
    "ivf_bq.per_chip_capacity_rows": 0.0,
    "ivf_bq.code_compression_x": 0.0,
    "ivf_bq.code_bytes_per_row": 0.0,
    "ivf_bq.recompiles_during_search": 0.0,
    "ivf_bq.recall": 0.01,
    "ivf_bq.per_chip_recall": 0.01,
    # SLO plane: availability and the recall estimate are promises, not
    # throughput — tiny slips are real regressions worth a row
    "serving.availability": 0.001,
    "serving.recall_estimate": 0.01,
    "serving.recall_stale": 0.0,
    "serving.recompiles_during_serving": 0.0,
    # flight recorder (round 19): ONE sustained straggler excursion in the
    # serving window is worth a row
    "serving.straggler_events": 0.0,
    # cost model (round 11): an unexplained retrace is a contract
    # violation at ANY count; prediction accuracy gets a 5% band before a
    # drift away from ratio 1.0 becomes a regression row
    "serving.unexplained_retraces": 0.0,
    # paged Pallas plane (round 16): ANY slip of paged-vs-packed
    # throughput below the prior round is a regression row
    "serving.paged_to_packed_qps_ratio": 0.0,
    "serving.hbm_predicted_to_measured": 0.05,
    "ivf_flat.hbm_predicted_to_measured": 0.05,
    "ivf_pq.hbm_predicted_to_measured": 0.05,
    "ivf_bq.hbm_predicted_to_measured": 0.05,
    # build fast path (round 17): the no-refine multi-bit recall is a
    # promise (the ≥0.95 rung), and the streamed build's peak-residency
    # prediction is a step function of the layout — ANY growth is a
    # margin loss on the per-chip share worth a row
    "bq_build.no_refine_recall": 0.01,
    "bq_build.build_peak_predicted_bytes": 0.0,
    "bq_build.sift1b_share_peak_predicted_bytes": 0.0,
    # capacity plane (round 18): ANY OOM verdict in the oversubscribed
    # chaos rung is the admission controller failing — zero tolerance;
    # unclassified residue likewise
    "capacity.oom_verdicts": 0.0,
    "capacity.unclassified": 0.0,
    # filtered search (round 20): the filtered-to-unfiltered throughput
    # ratio and the recompile count are contracts of the push-down path,
    # not throughput — ANY slip is a regression row; filtered recall gets
    # the same 1% band the family recalls use
    "filtered.ivf_flat.sel10.filtered_to_unfiltered_qps_ratio": 0.0,
    "filtered.ivf_flat.sel01.filtered_to_unfiltered_qps_ratio": 0.0,
    "filtered.ivf_bq.sel10.filtered_to_unfiltered_qps_ratio": 0.0,
    "filtered.ivf_bq.sel01.filtered_to_unfiltered_qps_ratio": 0.0,
    "filtered.ivf_flat.recompiles_during_filtered_search": 0.0,
    "filtered.ivf_bq.recompiles_during_filtered_search": 0.0,
    "filtered.ivf_flat.sel10.filtered_recall": 0.01,
    "filtered.ivf_flat.sel01.filtered_recall": 0.01,
    "filtered.ivf_bq.sel10.filtered_recall": 0.01,
    "filtered.ivf_bq.sel01.filtered_recall": 0.01,
    "filtered.hybrid.hybrid_recall": 0.01,
    # autotuning loop (round 21): a post-spike error-budget burn means the
    # controller left an SLO in breach — zero tolerance; the calm phase
    # acting at all is a livelock, likewise zero tolerance; the tuned
    # recall is a promise of the emitted operating point (1% band, like
    # the family recalls)
    "tuning.spike_budget_burn": 0.0,
    "tuning.calm_actions": 0.0,
    "tuning.unexplained_diagnoses": 0.0,
    "tuning.tuned_recall": 0.01,
}


def compare(a: dict, b: dict, threshold: float, per_metric: dict):
    """Rows of (metric, a, b, delta_frac, verdict), union of both runs."""
    rows = []
    for metric in sorted(set(a) | set(b)):
        va, vb = a.get(metric), b.get(metric)
        if va is None:
            rows.append((metric, None, vb, None, "new"))
            continue
        if vb is None:
            rows.append((metric, va, None, None, "gone"))
            continue
        if isinstance(va, str) or isinstance(vb, str):
            # string metric (roofline `bound` verdicts): a flip is
            # information worth a row, never a regression — the numeric
            # utilizations around it carry the gating
            rows.append((metric, va, vb, None, "·"))
            continue
        delta = (vb - va) / abs(va) if va else (0.0 if vb == va else None)
        dirn = direction(metric)
        thr = per_metric.get(metric, threshold)
        if dirn == "info":
            verdict = "·"
        elif delta is None:
            # from-zero transition (va == 0, vb != 0): no finite delta, but
            # the direction still decides — latency appearing from 0 is a
            # regression the gate must not wave through as informational
            verdict = ("improved" if (dirn == "up") == (vb > va)
                       else "regression")
        elif dirn == "one":
            # accuracy metric: best AT 1.0 — compare distances from 1
            ea, eb = abs(va - 1.0), abs(vb - 1.0)
            verdict = ("regression" if eb > ea + thr
                       else "improved" if eb < ea - thr else "ok")
        elif dirn == "up":
            verdict = ("regression" if delta < -thr
                       else "improved" if delta > thr else "ok")
        else:
            verdict = ("regression" if delta > thr
                       else "improved" if delta < -thr else "ok")
        rows.append((metric, va, vb, delta, verdict))
    return rows


def _fmt(v):
    if v is None:
        return "—"
    if isinstance(v, str):
        return v
    if abs(v) >= 1000:
        return f"{v:,.1f}"
    return f"{v:.4g}"


def markdown(rows, label_a, label_b, note_a, note_b, threshold) -> str:
    lines = [
        f"# Bench delta: {label_a} → {label_b}",
        "",
        f"Default regression threshold: ±{threshold:.0%} "
        f"(direction-aware; `·` = informational).",
    ]
    for label, note in ((label_a, note_a), (label_b, note_b)):
        if note:
            lines.append(f"- **{label}**: {note}")
    lines.append("")
    if not rows:
        lines.append("_No comparable metrics — nothing to diff._")
        return "\n".join(lines) + "\n"
    lines += [
        f"| metric | {label_a} | {label_b} | Δ | verdict |",
        "|---|---:|---:|---:|---|",
    ]
    order = {"regression": 0, "improved": 1, "ok": 2, "new": 3, "gone": 4,
             "·": 5}
    for metric, va, vb, delta, verdict in sorted(
            rows, key=lambda r: (order.get(r[4], 9), r[0])):
        d = "—" if delta is None else f"{delta:+.1%}"
        lines.append(
            f"| `{metric}` | {_fmt(va)} | {_fmt(vb)} | {d} | {verdict} |")
    n_reg = sum(1 for r in rows if r[4] == "regression")
    n_imp = sum(1 for r in rows if r[4] == "improved")
    lines += ["",
              f"**{n_reg} regression(s), {n_imp} improvement(s), "
              f"{len(rows)} metrics compared.**"]
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/bench_compare.py",
        description="Diff two bench runs into a markdown delta table.")
    ap.add_argument("run_a", help="older run (driver JSON / metric line / "
                                  "metrics JSONL)")
    ap.add_argument("run_b", help="newer run")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="default regression threshold as a fraction "
                         "(default 0.05 = 5%%)")
    ap.add_argument("--metric-threshold", action="append", default=[],
                    metavar="METRIC=FRAC",
                    help="per-metric override, repeatable "
                         "(e.g. ivf_pq.qps=0.02)")
    ap.add_argument("--output", default=None, metavar="PATH",
                    help="also write the markdown report here")
    ap.add_argument("--fail-on-regression", action="store_true",
                    help="exit 1 when any regression verdict exists")
    args = ap.parse_args(argv)

    per_metric = dict(_DEFAULT_METRIC_THRESHOLDS)
    for spec in args.metric_threshold:
        metric, _, frac = spec.partition("=")
        try:
            per_metric[metric.strip()] = float(frac)
        except ValueError:
            print(f"bench_compare: bad --metric-threshold {spec!r}",
                  file=sys.stderr)
            return 2

    label_a, line_a, note_a = load_run(args.run_a)
    label_b, line_b, note_b = load_run(args.run_b)
    if line_a is None and line_b is None:
        print(f"bench_compare: neither input is readable "
              f"({note_a}; {note_b})", file=sys.stderr)
        return 2

    rows = compare(metrics_of(line_a), metrics_of(line_b),
                   args.threshold, per_metric)
    report = markdown(rows, label_a, label_b, note_a, note_b, args.threshold)
    sys.stdout.write(report)
    if args.output:
        with open(args.output, "w") as f:
            f.write(report)
            f.flush()
    if args.fail_on_regression and any(r[4] == "regression" for r in rows):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
