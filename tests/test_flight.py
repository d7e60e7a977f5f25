"""Flight recorder tests (ISSUE 16): windowed operating-point sampling,
the telemetry-off NOOP gate, classified fault degradation + recovery
(round-7 invariant), straggler detection, frontier extraction, the CLI,
and the tier-1 end-to-end acceptance — the streaming bench section records
continuous windows whose frontier the real CLI extracts non-empty."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from raft_tpu import obs, resilience
from raft_tpu.obs import flight

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def telemetry():
    obs.reset()
    resilience.clear_faults()
    obs.enable()
    try:
        yield obs
    finally:
        resilience.clear_faults()
        obs.disable()
        obs.reset()


def _drain_events():
    """Read (and so age out) every resilience event recorded so far."""
    return resilience.recent_events()


# ---------------------------------------------------------------------------
# NOOP gate
# ---------------------------------------------------------------------------


def test_disabled_recorder_holds_zero_state(tmp_path):
    obs.reset()
    obs.disable()
    path = str(tmp_path / "off.jsonl")
    rec = flight.FlightRecorder(path, knobs={"algo": "x"})
    assert not rec.enabled
    assert rec.maybe_sample() is None and rec.sample() is None
    assert rec.records() == [] and rec.windows_recorded == 0
    assert rec.straggler_events == 0
    rec.start()
    rec.stop()
    # the contract is ZERO state, not merely inert: no ring, no providers,
    # no clock bookkeeping — and nothing on disk
    assert not hasattr(rec, "_ring")
    assert not hasattr(rec, "_knobs")
    assert not os.path.exists(path)


# ---------------------------------------------------------------------------
# windows, ring bound, interval gating
# ---------------------------------------------------------------------------


def test_windows_record_and_ring_caps(telemetry, tmp_path):
    path = str(tmp_path / "rec.jsonl")
    rec = flight.FlightRecorder(path, knobs={"algo": "ivf_flat", "k": 5},
                                interval_s=60.0, cap=4)
    for _ in range(6):
        rec.sample()
    assert rec.windows_recorded == 6
    ring = rec.records()
    assert len(ring) == 4  # bounded ring dropped the oldest two
    assert [r["window"] for r in ring] == [2, 3, 4, 5]
    # the JSONL stream keeps everything, opened by the clock handshake
    records = flight.read_recording(path)
    assert records[0]["type"] == "clock_offset"
    wins = [r for r in records if r["type"] == "flight_window"]
    assert [w["window"] for w in wins] == list(range(6))
    assert all(w["schema_version"] == flight.SCHEMA_VERSION for w in wins)
    fps = {w["fingerprint"]["fp"] for w in wins}
    assert len(fps) == 1  # one knob vector, one frontier group
    assert flight.validate(records) == []


def test_maybe_sample_interval_gating(telemetry):
    rec = flight.FlightRecorder(knobs={}, interval_s=10.0)
    assert rec.maybe_sample(now=100.0) is not None  # first is immediate
    assert rec.maybe_sample(now=105.0) is None
    assert rec.maybe_sample(now=109.9) is None
    assert rec.maybe_sample(now=110.1) is not None
    assert rec.windows_recorded == 2


def test_window_local_ops_are_deltas(telemetry):
    rec = flight.FlightRecorder(knobs={}, interval_s=0.0)
    obs.add("serving.requests.ok", 10)
    rec.sample(now=0.0)
    obs.add("serving.requests.ok", 7)
    win = rec.sample(now=2.0)
    # cumulative counter is 17, but the WINDOW saw 7 over 2 s
    assert win["ops"]["requests_ok"] == 7
    assert win["ops"]["qps"] == pytest.approx(3.5)


# ---------------------------------------------------------------------------
# classified degradation + recovery (round-7 invariant)
# ---------------------------------------------------------------------------


def test_armed_fault_degrades_classified_then_recovers(telemetry, tmp_path):
    path = str(tmp_path / "rec.jsonl")
    rec = flight.FlightRecorder(path, knobs={"algo": "x"}, interval_s=0.0)
    rec.sample()
    resilience.arm_faults("obs.flight.sample=oom:1")
    degraded = rec.sample()
    assert degraded["errors"]["sample"] == resilience.OOM
    assert degraded["window"] == 1  # the window survived as a stub
    clean = rec.sample()
    assert "errors" not in clean  # full recovery on the next sample
    assert clean["fingerprint"]["fp"]
    # a degraded-classified window is VALID — the recorder doing its job
    assert flight.validate(flight.read_recording(path)) == []


def test_broken_provider_degrades_one_section_only(telemetry):
    def bad_knobs():
        raise RuntimeError("knob source gone")

    rec = flight.FlightRecorder(knobs=bad_knobs, interval_s=0.0)
    win = rec.sample()
    assert win["errors"]["fingerprint"] == resilience.FATAL
    assert win["fingerprint"] is None
    assert isinstance(win["ops"], dict)  # the other sections still landed
    assert flight.validate([{"type": "clock_offset"}, win]) == []


def test_unwritable_stream_never_raises(telemetry, tmp_path):
    target = tmp_path / "dir_in_the_way"
    target.mkdir()  # export's open() will fail with IsADirectoryError
    rec = flight.FlightRecorder(str(target), knobs={}, interval_s=0.0)
    win = rec.sample()  # must not raise: durability lost, window kept
    assert rec.records()[-1] is win
    assert obs.snapshot()["counters"].get("flight.export_degraded") == 1


# ---------------------------------------------------------------------------
# health verdict rides the first window
# ---------------------------------------------------------------------------


def test_health_verdict_rides_window_zero(telemetry):
    rec = flight.FlightRecorder(
        knobs={}, interval_s=0.0,
        health={"healthy": True, "platform": "cpu"})
    w0 = rec.sample()
    assert w0["health"] == {"healthy": True, "platform": "cpu"}
    w1 = rec.sample()
    assert "health" not in w1  # first window only


def test_probe_health_uses_subprocess_probe(telemetry, monkeypatch):
    from raft_tpu.obs import health as obs_health

    class FakeVerdict:
        def as_dict(self):
            return {"healthy": True, "platform": "fake"}

    calls = []

    def fake_probe(platform, timeout=None):
        calls.append((platform, timeout))
        return FakeVerdict()

    monkeypatch.setattr(obs_health, "probe", fake_probe)
    rec = flight.FlightRecorder(knobs={}, interval_s=0.0, probe_health=True)
    w0 = rec.sample()
    assert w0["health"] == {"healthy": True, "platform": "fake"}
    assert calls == [("default", 10.0)]
    rec.sample()
    assert len(calls) == 1  # probed once, on window 0 only


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


def test_straggler_fires_after_consecutive_hot_windows(telemetry):
    rec = flight.FlightRecorder(knobs={}, interval_s=0.0)
    rec._ratio, rec._hot_needed = 4.0, 2
    _drain_events()
    obs.set_gauge("distributed.shard_skew", 8.0)
    w0 = rec.sample()
    assert "straggler" not in w0  # one hot window is not yet sustained
    w1 = rec.sample()
    assert w1["straggler"] == {"skew": 8.0, "windows": 2, "ratio": 4.0}
    assert rec.straggler_events == 1
    events = [e for e in _drain_events() if e["event"] == "straggler"]
    assert len(events) == 1 and events[0]["site"] == "obs.flight"
    # re-armed: the NEXT hot window alone must not fire again
    w2 = rec.sample()
    assert "straggler" not in w2 and rec.straggler_events == 1
    w3 = rec.sample()
    assert "straggler" in w3 and rec.straggler_events == 2


def test_straggler_resets_on_cool_window(telemetry):
    rec = flight.FlightRecorder(knobs={}, interval_s=0.0)
    rec._ratio, rec._hot_needed = 4.0, 2
    obs.set_gauge("distributed.shard_skew", 8.0)
    rec.sample()
    obs.set_gauge("distributed.shard_skew", 1.2)  # cools off
    rec.sample()
    obs.set_gauge("distributed.shard_skew", 8.0)  # hot again, count restarts
    rec.sample()
    assert rec.straggler_events == 0


def test_straggler_env_knobs(telemetry, monkeypatch):
    monkeypatch.setenv(flight.RATIO_ENV, "2.5")
    monkeypatch.setenv(flight.WINDOWS_ENV, "3")
    rec = flight.FlightRecorder(knobs={})
    assert rec._ratio == 2.5 and rec._hot_needed == 3
    monkeypatch.setenv(flight.RATIO_ENV, "garbage")
    monkeypatch.setenv(flight.WINDOWS_ENV, "-1")
    rec = flight.FlightRecorder(knobs={})
    assert rec._ratio == 4.0 and rec._hot_needed == 2  # defaults survive


# ---------------------------------------------------------------------------
# fingerprint + validate
# ---------------------------------------------------------------------------


def test_fingerprint_stable_under_dict_order():
    a = flight.fingerprint({"algo": "ivf_pq", "nprobe": 32, "k": 10})
    b = flight.fingerprint({"k": 10, "nprobe": 32, "algo": "ivf_pq"})
    assert a["fp"] == b["fp"]
    assert a["process_count"] >= 1  # fleet identity stamped in
    c = flight.fingerprint({"algo": "ivf_pq", "nprobe": 64, "k": 10})
    assert c["fp"] != a["fp"]


def test_validate_flags_structural_problems():
    assert flight.validate([]) == ["no flight_window records"]
    base = {"type": "flight_window", "window": 0, "t": 1.0,
            "schema_version": flight.SCHEMA_VERSION, "interval_s": 0.0,
            "fingerprint": {"fp": "abc"}, "ops": {}, "health": None}
    # missing handshake
    assert any("handshake" in p for p in flight.validate([dict(base)]))
    hs = {"type": "clock_offset", "process_index": 0}
    assert flight.validate([hs, dict(base)]) == []
    # unclassified degradation kind
    bad = dict(base, errors={"report": "whoops"})
    assert any("unclassified" in p for p in flight.validate([hs, bad]))
    # non-monotonic window ids
    recs = [hs, dict(base), dict(base, window=2), dict(base, window=1)]
    assert any("not increasing" in p for p in flight.validate(recs))
    # schema drift
    drift = dict(base, schema_version=99)
    assert any("schema_version" in p for p in flight.validate([hs, drift]))


# ---------------------------------------------------------------------------
# frontier extraction
# ---------------------------------------------------------------------------


def _win(w, fp, qps, p99, recall=None):
    rec = {"type": "flight_window", "window": w,
           "schema_version": flight.SCHEMA_VERSION, "interval_s": 1.0,
           "fingerprint": {"fp": fp, "algo": "x"},
           "ops": {"qps": qps, "p99_ub_s": p99, "requests_ok": 1}}
    if recall is not None:
        rec["report"] = {"recall": {"recall": recall, "ci_low": recall - .02,
                                    "ci_high": recall + .02}}
    return rec


def test_frontier_marks_pareto_points():
    records = [
        _win(0, "fast", 1000.0, 0.010, recall=0.90),
        _win(1, "fast", 1200.0, 0.012, recall=0.90),
        _win(2, "slowgood", 400.0, 0.005, recall=0.99),
        _win(3, "dominated", 300.0, 0.050, recall=0.80),
    ]
    out = flight.extract_frontier(records)
    assert out["points"] == 3
    by_fp = {g["fp"]: g for g in out["groups"]}
    # "fast" wins QPS, "slowgood" wins recall AND p99 — both non-dominated;
    # "dominated" loses on every axis to "slowgood"
    assert by_fp["fast"]["pareto"] and by_fp["slowgood"]["pareto"]
    assert not by_fp["dominated"]["pareto"]
    assert out["pareto_points"] == 2
    # per-group medians over the group's windows
    assert by_fp["fast"]["qps"] == 1200.0 and by_fp["fast"]["windows"] == 2
    assert by_fp["fast"]["recall"] == 0.90
    # pareto-first ordering
    assert [g["pareto"] for g in out["groups"]] == [True, True, False]


def test_frontier_nonempty_without_recall_plane():
    """A recording with no shadow sampler still yields a QPS/p99 frontier
    — missing axes compare equal-worst, never empty the Pareto set."""
    records = [_win(0, "a", 500.0, 0.01), _win(1, "b", 100.0, 0.10)]
    out = flight.extract_frontier(records)
    assert out["pareto_points"] >= 1
    assert {g["fp"] for g in out["groups"] if g["pareto"]} == {"a"}


def test_frontier_ignores_unfingerprinted_windows():
    rec = {"type": "flight_window", "window": 0, "fingerprint": None,
           "ops": {}, "schema_version": flight.SCHEMA_VERSION}
    out = flight.extract_frontier([rec])
    assert out["points"] == 0 and out["pareto_points"] == 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "raft_tpu.obs.flight", *args],
        capture_output=True, text=True, timeout=120, cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_cli_validate_render_frontier(telemetry, tmp_path):
    path = str(tmp_path / "rec.jsonl")
    rec = flight.FlightRecorder(path, knobs={"algo": "ivf_flat"},
                                interval_s=0.0)
    obs.add("serving.requests.ok", 5)
    for _ in range(3):
        rec.sample()
    fpath = str(tmp_path / "frontier.json")
    proc = _cli(path, "--validate", "--render", "--frontier", fpath)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "valid:" in proc.stderr
    assert "w  0" in proc.stdout  # rendered timeline rows
    frontier = json.load(open(fpath))
    assert frontier["type"] == "flight_frontier"
    assert frontier["pareto_points"] >= 1
    # clean -m execution: flight must not be pre-imported by the package
    assert "found in sys.modules" not in proc.stderr


def test_cli_rejects_empty_and_invalid(tmp_path):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert _cli(str(empty)).returncode == 2
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps(
        {"type": "flight_window", "window": 0, "schema_version": 99,
         "interval_s": 0.0, "fingerprint": {"fp": "x"}, "ops": {},
         "health": None}) + "\n")
    proc = _cli(str(bad), "--validate")
    assert proc.returncode == 1
    assert "INVALID" in proc.stderr


# ---------------------------------------------------------------------------
# tier-1 end-to-end: the streaming bench section records a frontier
# ---------------------------------------------------------------------------


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_for_flight_test", os.path.join(_REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_streaming_bench_records_frontier_end_to_end(
        telemetry, tmp_path, monkeypatch):
    """ISSUE 16 acceptance: the tiny streaming section runs with the
    recorder pumping continuous windows to results/flight_streaming.jsonl
    through the crash-safe channel, and the REAL CLI extracts a non-empty
    fingerprint-grouped Pareto frontier from that recording."""
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import health as obs_health

    # the measuring process holds the device: its verdict must come from
    # its own backend, never from a subprocess probe (a child cannot open
    # a chip its parent holds)
    def no_subprocess_probe(*a, **kw):
        raise AssertionError("subprocess health probe from a JAX process")

    monkeypatch.setattr(obs_health, "probe", no_subprocess_probe)
    monkeypatch.chdir(tmp_path)

    rng = np.random.default_rng(0)
    X = rng.standard_normal((1500, 16)).astype(np.float32)
    queries = rng.standard_normal((32, 16)).astype(np.float32)
    index = ivf_flat.build(X, ivf_flat.IvfFlatParams(n_lists=16,
                                                     list_size_cap=0))
    bench = _load_bench()
    monkeypatch.setenv(flight.INTERVAL_ENV, "0.05")
    out = bench._serving_streaming(index, queries, k=5, nprobe=2, tiny=True)

    assert out["flight_windows"] >= 3, out["flight_windows"]
    assert out["frontier_points"] >= 1
    assert out["flight_file"] == os.path.join("results",
                                              "flight_streaming.jsonl")
    records = flight.read_recording(out["flight_file"])
    assert flight.validate(records) == [], flight.validate(records)
    wins = [r for r in records if r["type"] == "flight_window"]
    assert len(wins) == out["flight_windows"]
    assert wins[0]["health"]["healthy"] is True
    assert wins[0]["health"]["backend"] == "cpu"
    # >= one window per offered load, each fingerprinted by ITS queue's
    # knob vector (batch cap 1 vs the dynamic cap => >= 2 groups)
    fps = {w["fingerprint"]["fp"] for w in wins
           if isinstance(w.get("fingerprint"), dict)}
    assert len(fps) >= 2, fps
    # the frontier artifact landed through the crash-safe channel
    frontier_disk = json.load(open(out["frontier_file"]))
    assert frontier_disk["pareto_points"] == out["frontier_points"]

    # the real CLI, end to end on the real recording
    proc = _cli(os.path.join(str(tmp_path), out["flight_file"]),
                "--validate", "--frontier",
                str(tmp_path / "frontier_cli.json"))
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    cli_frontier = json.load(open(tmp_path / "frontier_cli.json"))
    assert cli_frontier["pareto_points"] >= 1
    groups = {g["fp"] for g in cli_frontier["groups"]}
    assert groups == fps  # grouped BY fingerprint, all of them


# ---------------------------------------------------------------------------
# sampler vs span-feeder race (ISSUE 17 guarded-state fixes)
# ---------------------------------------------------------------------------


def test_sampler_and_span_feeders_race_cleanly(telemetry, tmp_path):
    """Two forced samplers race two span feeders: every window lands
    exactly once (the window counter, ring append, and helper reads all
    sit under the recorder lock — the guarded-state fix routed the event
    and health helpers through visible call sites inside it) and no
    window degrades."""
    import threading

    path = str(tmp_path / "race.jsonl")
    rec = flight.FlightRecorder(path, knobs={"algo": "x"},
                                interval_s=1e9, cap=64)
    stop = threading.Event()

    def feed():
        while not stop.is_set():
            with obs.record_span("serving.queue::serve"):
                obs.add("flight.fixture_feed")

    def pump(k):
        for _ in range(k):
            rec.sample()

    feeders = [threading.Thread(target=feed) for _ in range(2)]
    pumps = [threading.Thread(target=pump, args=(10,)) for _ in range(2)]
    for t in feeders + pumps:
        t.start()
    for t in pumps:
        t.join()
    stop.set()
    for t in feeders:
        t.join()

    assert rec.windows_recorded == 20
    ring = rec.records()
    assert sorted(r["window"] for r in ring) == list(range(20))
    assert not any("errors" in r for r in ring), ring
    # the JSONL stream holds the same 20 windows, once each
    on_disk = [r for r in flight.read_recording(path)
               if r.get("type") == "flight_window"]
    assert sorted(r["window"] for r in on_disk) == list(range(20))
