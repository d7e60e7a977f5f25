"""graftlint: JAX/TPU-aware static analysis for the raft_tpu tree.

Round 5 burned a scarce TPU bench window discovering failure classes that are
decidable from source alone — host syncs hiding in hot loops, Python control
flow on traced values, un-instrumented hot paths (round-5 review; the ROADMAP
"telemetry is a prerequisite" open item). This package is the cheap CPU-side
gate: an AST walk over the whole repo on every tier-1 run, with a pluggable
rule registry targeting this codebase's real bug classes and a checked-in
baseline so grandfathered findings stay visible-but-silent while any NEW
finding fails the build.

Layout (one module per concern):

* :mod:`raft_tpu.analysis.findings`    — Finding record + text/JSON report formats
* :mod:`raft_tpu.analysis.registry`    — pluggable rule registry (``@register``)
* :mod:`raft_tpu.analysis.jit_regions` — jit/pallas region resolver (which
  functions run under a tracer, incl. same-module call-graph reachability)
* :mod:`raft_tpu.analysis.walker`      — file discovery, parse, rule dispatch,
  inline ``# graftlint: ignore[rule]`` suppression
* :mod:`raft_tpu.analysis.baseline`    — grandfathered-finding store
* :mod:`raft_tpu.analysis.cli`         — ``python -m raft_tpu.analysis``
* :mod:`raft_tpu.analysis.rules`       — the rule catalog

Usage::

    python -m raft_tpu.analysis raft_tpu tests bench.py scripts
    python -m raft_tpu.analysis --list-rules
    python -m raft_tpu.analysis --json raft_tpu

Exit codes: 0 = no new findings, 1 = new findings (not in the baseline),
2 = bad invocation. Regenerate the baseline DELIBERATELY via
``scripts/analysis_baseline.py`` — never automatically.
"""

from raft_tpu.analysis.findings import Finding, Severity, format_json, format_text
from raft_tpu.analysis.registry import Rule, all_rules, get_rule, register
from raft_tpu.analysis.walker import ModuleContext, analyze_paths, collect_files
from raft_tpu.analysis.baseline import Baseline

__all__ = [
    "Baseline",
    "Finding",
    "ModuleContext",
    "Rule",
    "Severity",
    "all_rules",
    "analyze_paths",
    "collect_files",
    "format_json",
    "format_text",
    "get_rule",
    "register",
]
