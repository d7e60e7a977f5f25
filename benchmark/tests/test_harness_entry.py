"""The search-entry readers (``entry_ms.batch``, ``launch_ms.batch``,
``launches.batch``, ``idle_in_entry.batch``) on a synthetic trace with
known answers."""

import types

import pytest

import run
import trace_reduce

READERS = ("entry_ms.batch", "launch_ms.batch", "launches.batch",
           "idle_in_entry.batch")

# two IVF-PQ + refine batches in a 20 us slice (all times in us): each is
# ivf_pq::search (one launch) then refine::refine (one launch) then the
# harness's bench::wait. The device runs [0, 1), [2, 10) and [10, 18), so
# it idles [1, 2) inside the first batch's ivf_pq::search and [18, 20)
# inside the second batch's bench::wait.
DEVICE_OPS = [("fusion.1", 0, 1), ("_strip_class_call.2", 2, 10),
              ("fusion.3", 10, 18)]
ENTRY_AND_LAUNCH = [
    ("ivf_pq::search", 0, 2), ("ivf_pq::launch", 0.5, 1.5),
    ("refine::refine", 2, 3), ("refine::launch", 2.2, 2.6),
    ("ivf_pq::search", 10, 12), ("ivf_pq::launch", 10.5, 11.5),
    ("refine::refine", 12, 13), ("refine::launch", 12.2, 12.6),
]
HARNESS = [("bench::traced", 0, 20), ("bench::wait", 3, 10),
           ("bench::wait", 13, 20), ("$python frame", 0, 20)]
MARKS = {"start": {"n_done": 3, "counters": {"ivf_pq.search.launches": 10,
                                            "refine.launches": 5}},
         "stop": {"n_done": 5, "counters": {"ivf_pq.search.launches": 12,
                                           "refine.launches": 7}}}


def _plane(pid, name, line, events):
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    evs = "".join(
        f"    events {{ metadata_id: {ids[n]} offset_ps: {round(s * 1e6)} "
        f"duration_ps: {round((e - s) * 1e6)} }}\n" for n, s, e in events)
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())
    return (f'planes {{\n  id: {pid} name: "{name}"\n'
            f'  lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}  }}\n'
            f'{meta}}}\n')


def _reduction(host):
    from jax.profiler import ProfileData

    text = _plane(1, "/device:TPU:0", "XLA Ops", DEVICE_OPS) \
        + _plane(2, "/host:CPU", "python3", host)
    return trace_reduce.Reduction(ProfileData.from_text_proto(text))


def _read(name, host, marks=MARKS):
    ctx = types.SimpleNamespace(trace=_reduction(host), marks=marks,
                                log=print)
    return run.load_module(f"metrics/{name}.py").read(ctx)


def test_entry_ms_is_the_entry_union_per_batch():
    # [0, 3) and [10, 13): 6 us over 2 batches
    assert _read("entry_ms.batch", HARNESS + ENTRY_AND_LAUNCH) == \
        pytest.approx(3e-3)


def test_launch_ms_is_the_launch_union_per_batch():
    # 1 + 0.4 + 1 + 0.4 us over 2 batches
    assert _read("launch_ms.batch", HARNESS + ENTRY_AND_LAUNCH) == \
        pytest.approx(1.4e-3)


def test_launches_is_the_counter_rise_per_batch():
    assert _read("launches.batch", HARNESS + ENTRY_AND_LAUNCH) == 2.0


def test_idle_in_entry_counts_only_the_gap_inside_an_entry():
    # [1, 2) of the 20 us slice; the [18, 20) gap is the harness's wait
    value = _read("idle_in_entry.batch", HARNESS + ENTRY_AND_LAUNCH)
    assert value == pytest.approx(5.0)
    t = _reduction(HARNESS + ENTRY_AND_LAUNCH)
    assert 100.0 * (1 - t.busy_s / t.window_s) == pytest.approx(15.0)
    assert [g[0] for g in t.idle_gaps()] == ["bench::wait", "ivf_pq::launch"]


@pytest.mark.parametrize("name", READERS)
def test_no_entry_span_reads_nothing(name):
    assert _read(name, HARNESS) is None


@pytest.mark.parametrize("name", ["launch_ms.batch", "launches.batch"])
def test_entry_without_launches_reads_nothing(name):
    """A program whose entry has spans but no launch spans or counters
    (as before they existed) reads nothing for the launch metrics."""
    entries = [s for s in ENTRY_AND_LAUNCH if s[0].endswith("::search")]
    no_counts = {k: dict(v, counters={}) for k, v in MARKS.items()}
    assert _read(name, HARNESS + entries, no_counts) is None
    assert _read("entry_ms.batch", HARNESS + entries, no_counts) == \
        pytest.approx(2e-3)


@pytest.mark.parametrize("name", READERS)
def test_untraced_or_unmarked_reads_nothing(name):
    mod = run.load_module(f"metrics/{name}.py")
    assert mod.read(types.SimpleNamespace(trace=None, marks={})) is None
    ctx = types.SimpleNamespace(trace=_reduction(HARNESS + ENTRY_AND_LAUNCH),
                                marks={"start": MARKS["start"]})
    assert mod.read(ctx) is None
