"""Span-tree tracing tests (round-8 ISSUE 5 acceptance criteria).

Covers: contextvar parenting + attribute round-trip, exception-safe
classified spans (enabled AND disabled — the satellite regression test),
the bounded ring, Chrome trace-event export from an instrumented ivf_pq
build+search (≥3-level tree: entry → phase → tile), the launch spans and
counters inside the search entries (entry → scan → one launch per program),
spans on the profiler's own host plane, the device-stage ``named_scope``s
in the lowered search program, and the histogram percentile upper
bounds."""

import json
import re
import threading

import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.obs import tracing


@pytest.fixture
def telemetry():
    """Enabled gate + clean registry/ring before and after."""
    obs.reset()
    obs.clear_spans()
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()
        obs.reset()
        obs.clear_spans()


def _by_name(records):
    out = {}
    for rec in records:
        out.setdefault(rec["name"], rec)
    return out


def _depth(rec, records):
    ids = {r["span_id"]: r for r in records}
    depth, pid = 1, rec["parent_id"]
    while pid is not None:
        rec = ids[pid]
        depth += 1
        pid = rec["parent_id"]
    return depth


# ---------------------------------------------------------------------------
# tree structure + attributes
# ---------------------------------------------------------------------------


def test_span_tree_parenting_and_attrs(telemetry):
    with obs.record_span("unit::entry", attrs={"rows": 128}):
        with obs.record_span("unit::phase"):
            with obs.record_span("unit::tile") as sp:
                sp.set_attr("tile", 3)
    recs = obs.spans()
    assert [r["name"] for r in recs] == \
        ["unit::tile", "unit::phase", "unit::entry"]  # close order
    by = _by_name(recs)
    assert by["unit::entry"]["parent_id"] is None
    assert by["unit::phase"]["parent_id"] == by["unit::entry"]["span_id"]
    assert by["unit::tile"]["parent_id"] == by["unit::phase"]["span_id"]
    # one trace spans the whole tree; attrs round-trip
    assert len({r["trace_id"] for r in recs}) == 1
    assert by["unit::entry"]["attrs"] == {"rows": 128}
    assert by["unit::tile"]["attrs"] == {"tile": 3}
    assert all(r["dur_s"] >= 0.0 for r in recs)


def test_sibling_spans_share_parent(telemetry):
    with obs.record_span("unit::entry"):
        with obs.record_span("unit::a"):
            pass
        with obs.record_span("unit::b"):
            pass
    by = _by_name(obs.spans())
    assert by["unit::a"]["parent_id"] == by["unit::entry"]["span_id"]
    assert by["unit::b"]["parent_id"] == by["unit::entry"]["span_id"]
    assert by["unit::a"]["span_id"] != by["unit::b"]["span_id"]


def test_new_thread_starts_new_trace(telemetry):
    done = threading.Event()

    def worker():
        with obs.record_span("unit::threaded"):
            pass
        done.set()

    with obs.record_span("unit::main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert done.wait(1)
    by = _by_name(obs.spans())
    assert by["unit::threaded"]["parent_id"] is None
    assert by["unit::threaded"]["trace_id"] != by["unit::main"]["trace_id"]


# ---------------------------------------------------------------------------
# exception safety + classification (satellite: raise-inside-record_span
# must be covered for BOTH enabled and disabled telemetry)
# ---------------------------------------------------------------------------


def test_span_raise_enabled_records_and_classifies(telemetry):
    with pytest.raises(RuntimeError):
        with obs.record_span("unit::oom"):
            raise RuntimeError("RESOURCE_EXHAUSTED: hbm over budget")
    with pytest.raises(ValueError):
        with obs.record_span("unit::bug"):
            raise ValueError("shape mismatch")
    snap = obs.snapshot()
    # durations recorded despite the raise
    assert snap["timers"]["unit::oom"]["count"] == 1
    assert snap["timers"]["unit::bug"]["count"] == 1
    # spans tagged with the resilience.classify kind + error counters
    by = _by_name(obs.spans())
    assert by["unit::oom"]["error"] == "oom"
    assert by["unit::bug"]["error"] == "fatal"
    assert snap["counters"]["span.errors.oom"] == 1
    assert snap["counters"]["span.errors.fatal"] == 1


def test_span_raise_disabled_is_pure_passthrough():
    assert not obs.enabled()
    obs.clear_spans()
    span = obs.record_span("unit::never")
    assert span is obs.NOOP_SPAN
    with pytest.raises(RuntimeError):
        with span:
            raise RuntimeError("boom")
    # nothing recorded anywhere: registry, ring, or error counters
    assert obs.snapshot() == {"counters": {}, "timers": {}, "histograms": {},
            "gauges": {}}
    assert obs.spans() == []
    assert obs.NOOP_SPAN.set_attr("k", 1) is obs.NOOP_SPAN


# ---------------------------------------------------------------------------
# ring bound
# ---------------------------------------------------------------------------


def test_span_ring_is_bounded(telemetry):
    cap = tracing._SPANS.maxlen
    assert cap and cap > 0
    for i in range(cap + 100):
        tracing.push_span({"name": "unit::flood", "span_id": str(i),
                           "parent_id": None, "trace_id": "t", "t0": 0.0,
                           "dur_s": 0.0})
    recs = obs.spans()
    assert len(recs) == cap
    # oldest entries evicted, newest kept
    assert recs[-1]["span_id"] == str(cap + 99)


# ---------------------------------------------------------------------------
# Chrome trace export (the acceptance test: instrumented ivf_pq build+search
# → parseable Perfetto JSON with a ≥3-level span tree and attr round-trip)
# ---------------------------------------------------------------------------


def test_ivf_pq_trace_export_acceptance(telemetry, rng, tmp_path):
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_pq

    data = jnp.asarray(rng.standard_normal((512, 16), dtype=np.float32))
    queries = jnp.asarray(rng.standard_normal((8, 16), dtype=np.float32))
    index = ivf_pq.build(data, ivf_pq.IvfPqParams(n_lists=8, pq_dim=8))
    vals, _ = ivf_pq.search(index, queries, 5, n_probes=4)
    np.asarray(vals)  # force completion inside the traced session

    path = str(tmp_path / "trace_ivf_pq.json")
    obs.export_chrome_trace(path, extra={"run": "tier1"})
    with open(path) as f:
        doc = json.load(f)  # must parse as strict JSON

    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert events, "no span events exported"
    # rebuild the tree from the exported args (round-trip, not the ring)
    by_id = {e["args"]["span_id"]: e for e in events}

    def depth(e):
        d, pid = 1, e["args"]["parent_id"]
        while pid is not None:
            e = by_id[pid]
            d += 1
            pid = e["args"]["parent_id"]
        return d

    names = {e["name"] for e in events}
    assert {"ivf_pq::build", "ivf_pq::encode", "ivf_pq::encode_tile",
            "ivf_pq::search", "ivf_pq::scan"} <= names
    # entry → phase → tile: the tile span sits ≥3 levels deep
    tile = next(e for e in events if e["name"] == "ivf_pq::encode_tile")
    assert depth(tile) >= 3
    # typed attributes round-trip through the file
    encode = next(e for e in events if e["name"] == "ivf_pq::encode")
    assert encode["args"]["rows"] == 512
    scan = next(e for e in events if e["name"] == "ivf_pq::scan")
    assert scan["args"]["backend"] == "gather"
    assert scan["args"]["queries"] == 8 and scan["args"]["probes"] == 4
    # timestamps are microseconds and parent intervals contain children
    build = next(e for e in events if e["name"] == "ivf_pq::build")
    assert build["ts"] <= tile["ts"]
    assert build["ts"] + build["dur"] >= tile["ts"]
    assert doc["otherData"]["run"] == "tier1"


def test_chrome_trace_includes_resilience_instants(telemetry):
    from raft_tpu import resilience

    resilience.clear_events()
    try:
        with obs.record_span("unit::recovering"):
            resilience.record_event("degraded_tile", site="unit.test",
                                    from_size=8, to_size=4)
        doc = obs.chrome_trace()
        inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert any(e["name"] == "degraded_tile" and
                   e["args"]["site"] == "unit.test" and
                   e["args"]["to_size"] == 4 and e["ts"] > 0
                   for e in inst)
    finally:
        resilience.clear_events()


# ---------------------------------------------------------------------------
# launch spans + counters inside the search entries
# ---------------------------------------------------------------------------


def _children(records, parent, name):
    return [r for r in records
            if r["name"] == name and r["parent_id"] == parent["span_id"]]


def _ivf(family, rng, n=3000, dim=16):
    from raft_tpu.neighbors import ivf_flat, ivf_pq

    X = rng.normal(size=(n, dim)).astype(np.float32)
    if family == "ivf_flat":
        idx = ivf_flat.build(X, ivf_flat.IvfFlatParams(n_lists=8,
                                                       group_size=512))
        return ivf_flat, X, idx
    idx = ivf_pq.build(X, ivf_pq.IvfPqParams(n_lists=8, pq_dim=8,
                                             group_size=512))
    return ivf_pq, X, idx


@pytest.mark.parametrize("family,backend,launches", [
    ("ivf_flat", "ragged", 2), ("ivf_flat", "gather", 1),
    ("ivf_pq", "ragged", 2), ("ivf_pq", "gather", 1)])
def test_search_entry_records_one_launch_span_per_program(
        telemetry, rng, family, backend, launches):
    """entry > scan > one ``<family>::launch`` per jitted program call, and
    ``<family>.search.launches`` counts them: 600 queries under a 1-byte
    workspace make two ragged tiles (512-query floor), the gather path one
    program."""
    from raft_tpu.core.resources import Resources

    mod, X, idx = _ivf(family, rng)
    Q = rng.normal(size=(600, 16)).astype(np.float32)
    obs.reset()
    obs.clear_spans()
    mod.search(idx, Q, 5, n_probes=2, backend=backend,
               res=Resources(workspace_bytes=1))
    recs = obs.spans()
    (entry,) = [r for r in recs if r["name"] == f"{family}::search"]
    (scan,) = _children(recs, entry, f"{family}::scan")
    assert len(_children(recs, scan, f"{family}::launch")) == launches
    assert len([r for r in recs if r["name"] == f"{family}::launch"]) \
        == launches
    assert obs.snapshot()["counters"][f"{family}.search.launches"] \
        == launches


@pytest.mark.parametrize("family,k,sorted_route", [
    ("ivf_flat", 10, True), ("ivf_pq", 40, True), ("ivf_flat", 1, False)])
def test_ragged_search_counts_topk_routes(telemetry, rng, family, k,
                                          sorted_route):
    """Per launch, ``strip_scan.topk.calls`` counts the length-class kernel
    calls of the class layout and ``strip_scan.topk.sorted`` those on the
    sorted top-kf route: all of them at the cells' kf, none at kf = 1."""
    from raft_tpu.core.resources import Resources

    mod, X, idx = _ivf(family, rng)
    Q = rng.normal(size=(600, 16)).astype(np.float32)
    obs.reset()
    mod.search(idx, Q, k, n_probes=2, backend="ragged",
               res=Resources(workspace_bytes=1))
    counters = obs.snapshot()["counters"]
    classes = idx._ragged_static_cache[0]
    assert counters[f"{family}.search.launches"] == 2
    assert counters["strip_scan.topk.calls"] == 2 * len(classes) > 0
    assert counters.get("strip_scan.topk.sorted", 0) == (
        counters["strip_scan.topk.calls"] if sorted_route else 0)
    # the CPU takes the jnp top-k routes, not the resident kernel
    assert counters["select_k.resident.calls"] == 0


@pytest.mark.parametrize("n_lists,p,k,algo,per_launch", [
    (1024, 16, 10, "exact", 2),     # IVF-Flat cell: coarse top-16, merge
    (1024, 16, 40, "exact", 2),     # IVF-PQ cell
    (1024, 16, 65, "exact", 1),     # k past the kernel: the probe only
    (1024, 64, 40, "exact", 1),     # p·kf = 2560 past the packed merge
    (1024, 16, 10, "approx", 1),    # an approximate probe: the merge only
    (8192, 16, 10, "exact", 1),     # too many lists for the packed probe
    (1024, 16, 200, "approx", 0),
])
def test_resident_counter_per_launch(telemetry, n_lists, p, k, algo,
                                     per_launch):
    """``select_k.resident.calls`` counts, per launch and from the static
    shapes alone, the top-k calls that run the resident kernel on the
    chip: the coarse probe's select and the merge."""
    from raft_tpu.ops.select_k import probe_algo
    from raft_tpu.ops.strip_scan import note_topk_routes

    obs.reset()
    note_topk_routes(((1, 1),), k, False, 3000, 1000, n_lists, p, k,
                     probe_algo(algo, False, n_lists), False)
    assert obs.snapshot()["counters"]["select_k.resident.calls"] == \
        3 * per_launch


def test_refine_records_entry_and_launch_spans(telemetry, rng):
    """``ivf_pq.search`` (two ragged tiles) then ``refine.refine``:
    ``refine::refine`` > ``refine::launch``, one refine program."""
    from raft_tpu.core.resources import Resources
    from raft_tpu.neighbors import refine

    mod, X, idx = _ivf("ivf_pq", rng)
    Q = rng.normal(size=(600, 16)).astype(np.float32)
    obs.reset()
    obs.clear_spans()
    _, cand = mod.search(idx, Q, 20, n_probes=2, backend="ragged",
                         res=Resources(workspace_bytes=1))
    d, ids = refine.refine(X, Q, cand, 5)
    assert ids.shape == (600, 5)
    recs = obs.spans()
    (entry,) = [r for r in recs if r["name"] == "refine::refine"]
    assert entry["parent_id"] is None
    assert len(_children(recs, entry, "refine::launch")) == 1
    counters = obs.snapshot()["counters"]
    assert counters["refine.launches"] == 1
    assert counters["ivf_pq.search.launches"] == 2


def test_launch_spans_and_counters_off_are_noops(rng):
    """Telemetry off: every span is the shared NOOP_SPAN and no counter
    moves, through whole searches and refine."""
    from raft_tpu.core.resources import Resources
    from raft_tpu.neighbors import refine

    assert not obs.enabled()
    assert obs.record_span("ivf_flat::launch") is obs.NOOP_SPAN
    assert obs.record_launch("ivf_flat::launch",
                             "ivf_flat.search.launches") is obs.NOOP_SPAN
    flat, X, fidx = _ivf("ivf_flat", rng)
    pq, _, pidx = _ivf("ivf_pq", rng)
    Q = rng.normal(size=(600, 16)).astype(np.float32)
    obs.reset()
    obs.clear_spans()
    flat.search(fidx, Q, 5, n_probes=2, backend="ragged",
                res=Resources(workspace_bytes=1))
    _, cand = pq.search(pidx, Q, 20, n_probes=2, backend="ragged")
    refine.refine(X, Q, cand, 5)
    assert obs.snapshot()["counters"] == {}
    assert obs.spans() == []


def test_span_lands_on_the_profiler_host_plane(telemetry, tmp_path):
    """A span recorded under an active ``jax.profiler`` trace is a host
    event of the same name in the ``.xplane.pb`` — on the device trace's
    clock, which is how the benchmark reads it."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.record_span("unit::profiled"):
            with obs.record_launch("unit::launch", "unit.launches"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"unit::profiled", "unit::launch"} <= names
    assert obs.snapshot()["counters"]["unit.launches"] == 1


# ---------------------------------------------------------------------------
# device-stage scopes in the lowered programs (HLO op_name metadata)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ragged_fused_hlo():
    """HLO text, with metadata, of ``ivf_flat._ragged_fused`` lowered for
    one small tile (interpret mode, as on the CPU)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.ops import strip_scan as ss

    n_lists, m, dim = 8, 512, 16
    classes, cls_ord = ss.class_info(np.full(n_lists, 300), dim=dim)
    f32 = jnp.float32
    return ivf_flat._ragged_fused.lower(
        jax.ShapeDtypeStruct((64, dim), f32),
        jax.ShapeDtypeStruct((n_lists, dim), f32),
        jax.ShapeDtypeStruct((n_lists, m, dim), f32),
        jax.ShapeDtypeStruct((n_lists, m), f32),
        jax.ShapeDtypeStruct((n_lists, m), jnp.int32),
        jax.ShapeDtypeStruct((n_lists,), jnp.int32),
        k=5, n_probes=2, metric="sqeuclidean", select_algo="exact",
        compute_dtype=None, classes=tuple(classes),
        class_counts=ss.class_counts_of(cls_ord, len(classes)),
        interpret=True).as_text(dialect="hlo", debug_info=True)


def _has_scope(hlo: str, scope: str) -> bool:
    """An op whose ``op_name`` names ``scope`` (a nested jit's ops carry
    names relative to their own computation: ``coarse_probe/dot_general``;
    inlined ones the caller's path: ``jit(_ragged_fused)/merge/add``)."""
    return re.search(rf'op_name="(?:[^"]*/)?{scope}/', hlo) is not None


@pytest.mark.parametrize("scope", ["coarse_probe", "strip_plan",
                                   "strip_scan", "merge", "finalize"])
def test_ragged_fused_carries_stage_scope(ragged_fused_hlo, scope):
    assert _has_scope(ragged_fused_hlo, scope)
    assert not _has_scope(ragged_fused_hlo, scope + "_absent")


def test_refine_program_carries_refine_scope():
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors import refine

    text = refine._refine_impl.lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((100, 16), jnp.float32),
        jax.ShapeDtypeStruct((8, 20), jnp.int32),
        k=5, metric="sqeuclidean", q_tile=8,
    ).as_text(dialect="hlo", debug_info=True)
    assert _has_scope(text, "refine")


# ---------------------------------------------------------------------------
# histogram percentile upper bounds (satellite 1)
# ---------------------------------------------------------------------------


def test_histogram_percentile_upper_bounds(telemetry):
    values = list(range(1, 101))
    for v in values:
        obs.observe("unit.lat", v)
    h = obs.snapshot()["histograms"]["unit.lat"]
    for key, q in (("p50_ub", 50), ("p90_ub", 90), ("p99_ub", 99)):
        true_q = float(np.percentile(values, q))
        # documented contract: an UPPER bound, within 2× of the truth
        assert h[key] >= true_q, (key, h[key], true_q)
        assert h[key] <= 2.0 * true_q, (key, h[key], true_q)
    # export carries the same derived keys
    assert h["p50_ub"] == 64.0


def test_export_jsonl_carries_process_stamp(telemetry, tmp_path,
                                            monkeypatch):
    monkeypatch.setenv("RAFT_TPU_PROCESS_INDEX", "3")
    monkeypatch.setenv("RAFT_TPU_PROCESS_COUNT", "8")
    obs.add("unit.rows", 7)
    rec = obs.export_jsonl(str(tmp_path / "m.jsonl"))
    assert rec["process_index"] == 3
    assert rec["process_count"] == 8
    line = json.loads((tmp_path / "m.jsonl").read_text())
    assert line["counters"]["unit.rows"] == 7
    assert line["process_index"] == 3
