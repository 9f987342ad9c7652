"""Device time by named scope, host spans and the host-device clock offset
(`bench/scopes.py`), and the wire-format trace reader under it
(`bench/xspace.py`), on synthetic traces and on traces recorded on a TPU
v5e."""
import gzip
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import scopes  # noqa: E402
import tracereduce  # noqa: E402
import xspace  # noqa: E402

DATA = BENCH / "tests" / "data"
SMALL = DATA / "small.xplane.pb"
SCOPED = DATA / "scoped.xplane.pb.gz"
MS = 1_000_000


@pytest.fixture
def scoped_dir(tmp_path):
    """The recorded scoped trace, unpacked where `reduce_dir` finds it."""
    out = tmp_path / "scoped.xplane.pb"
    out.write_bytes(gzip.decompress(SCOPED.read_bytes()))
    return tmp_path


# ------------------------------------------------------------------ reader

def _events(planes):
    return [(p.name, line.name, ev.name, ev.start_ns, ev.end_ns)
            for p in planes for line in p.lines for ev in line.events]


@pytest.mark.parametrize("recorded", [SMALL, SCOPED], ids=["small", "scoped"])
def test_reader_gives_the_events_of_profile_data(recorded, tmp_path):
    import jax
    path = recorded
    if recorded.suffix == ".gz":
        path = tmp_path / recorded.stem
        path.write_bytes(gzip.decompress(recorded.read_bytes()))
    mine = xspace.read(path)
    theirs = jax.profiler.ProfileData.from_file(str(path)).planes
    assert _events(mine) == _events(theirs)
    assert len(_events(mine)) > 10


def test_reader_decodes_event_and_metadata_statistics():
    planes = {p.name: p for p in xspace.read(SMALL)}
    lines = {line.name: line for line in planes["/device:TPU:0"].lines}
    runs = [ev.stats["run_id"] for ev in lines["XLA Modules"].events]
    assert runs == [68, 69, 70]
    tf_ops = {ev.metadata.get("tf_op") for ev in lines["XLA Ops"].events}
    assert tf_ops == {"jit(f)/dot_general:", None}


# ------------------------------------------------------------------ scopes

@pytest.mark.parametrize("tf_op,scope", [
    ("jit(f)/dot_general:", None),
    ("jit(_sampled_chunk_forward)/sample/s0b0/jit(_uniform)/add:", "sample"),
    ("jit(_sampled_chunk_forward)/s1b0/jit(ensemble_apply)/dot_general:",
     "s1b0"),
    ("jit(qat_step)/transpose(jvp(s0b0))/mul:", "s0b0"),
    ("jit(qat_step)/jvp(train_planes)/sub:", "train_planes"),
    ("jit(qat_step)/transpose(jvp(s2pool))/select_and_scatter:", "s2pool"),
    ("jit(qat_step)/adamw/sqrt:", "adamw"),
    ("jit(f)/head/dot_general:", "head"),
    ("jit(f)/stem/conv_general_dilated:", "stem"),
    ("jit(loss_scale)/mul:", None),
])
def test_scope_of_takes_the_outermost_recognised_component(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def _synthetic():
    """Two executions of one program on the device clock; the device
    clock reads 2-4 ms behind the host's."""
    ops = [("copy.1", 0, 5 * MS, None),
           ("fusion.2", 5 * MS, 20 * MS, "jit(f)/sample/add:"),
           ("copy.3", 20 * MS, 25 * MS, None),
           ("fusion.4", 25 * MS, 45 * MS, "jit(f)/transpose(jvp(s0b0))/mul:"),
           ("fusion.5", 45 * MS, 50 * MS, "jit(f)/iota:"),
           ("copy.6", 50 * MS, 100 * MS, None),
           ("copy.7", 200 * MS, 210 * MS, None)]
    devices = {"/device:TPU:0": [(n, s, e, "jit_f(9)") for n, s, e, _ in ops]}
    spans = [("bench.window", 0, 300 * MS),
             ("bench.run_mc_detector", 0, 300 * MS),
             ("repro.mc.score", 120 * MS, 152 * MS),
             ("repro.mc.planes", 152 * MS, 160 * MS),
             ("repro.mc.dispatch", 160 * MS, 161 * MS),
             ("repro.mc.dispatch", 290 * MS, 310 * MS)]
    return scopes.Trace(
        spans=spans, devices=devices,
        tf_ops={"/device:TPU:0": [t for *_, t in ops]},
        modules={"/device:TPU:0": [(0, 100 * MS, 1), (200 * MS, 300 * MS, 2)]},
        enqueued={(0, 1): 2 * MS, (0, 2): 201 * MS},
        completed={(0, 1): 104 * MS, (0, 2): 305 * MS})


def test_ops_with_no_tf_op_go_to_the_next_scoped_op():
    seconds, raw = scopes.scope_seconds(_synthetic(), 0, 300 * MS)
    # copy.1 -> sample; copy.3 -> s0b0; copy.6 has no scoped op after it
    # and goes to the last one (s0b0); copy.7's execution has none: other
    assert seconds == pytest.approx({"sample": 0.020, "s0b0": 0.075,
                                     "other": 0.015})
    assert raw == pytest.approx(0.070)


def test_clock_offset_interval_from_enqueue_and_completion():
    """Execution 1 bounds the offset to [2, 4] ms, execution 2 to [1, 5]."""
    assert scopes.clock_segments(_synthetic()) == [(0, 2 * MS, 4 * MS)]


def test_a_step_in_the_clock_alignment_starts_a_segment():
    """Execution 2 bounds the offset to [5, 6] ms, which [2, 4] ms
    excludes: two segments.  The device's idle 210-300 ms lies in the
    second: its middle, 255 ms, is 260.5 ms on the host clock."""
    t = _synthetic()
    t.enqueued[(0, 2)], t.completed[(0, 2)] = 205 * MS, 306 * MS
    t.spans = t.spans + [("repro.mc.wait", 259 * MS, 262 * MS)]
    assert scopes.clock_segments(t) == [(0, 2 * MS, 4 * MS),
                                        (200 * MS, 5 * MS, 6 * MS)]
    r = scopes.reduce(t)
    assert r["clock_offset_s"] == pytest.approx([0.002, 0.006])
    assert [pytest.approx(seg) for seg in r["clock_segments"]] == [
        [0.0, 0.002, 0.004], [0.2, 0.005, 0.006]]
    # the 100-200 ms gap lies in the first segment, as before
    assert r["idle_gaps"][:2] == [["repro.mc.planes", pytest.approx(0.100)],
                                  ["repro.mc.wait", pytest.approx(0.090)]]


def test_gaps_are_named_on_the_host_clock():
    """The device's idle 100-200 ms has its middle at 150 ms, which is
    153 ms on the host clock: inside `repro.mc.planes`, not the score."""
    r = scopes.reduce(_synthetic())
    assert r["clock_offset_s"] == pytest.approx([0.002, 0.004])
    assert r["idle_gaps"][0] == ["repro.mc.planes", pytest.approx(0.100)]
    assert r["spans"]["repro.mc.dispatch"] == {
        "count": 2, "seconds": pytest.approx(0.011)}
    assert "bench.window" not in r["spans"]
    plain = tracereduce.reduce_trace(_synthetic().spans,
                                     _synthetic().devices)
    assert plain["idle_gaps"][0] == ["repro.mc.score", pytest.approx(0.100)]
    for key in ("busy_s", "window_s", "device_ops", "programs"):
        assert r[key] == plain[key]


def test_a_trace_without_offset_pairs_keeps_the_plain_gap_names():
    t = _synthetic()
    t.enqueued, t.completed = {}, {}
    r = scopes.reduce(t)
    assert r["clock_offset_s"] == [0.0, 0.0]
    assert r["idle_gaps"] == tracereduce.reduce_trace(
        t.spans, t.devices)["idle_gaps"]


def test_a_trace_without_a_window_is_read_whole():
    t = _synthetic()
    t.spans = [s for s in t.spans if s[0] != "bench.window"]
    r = scopes.reduce(t)
    assert r["window_s"] == pytest.approx(0.310)
    t.spans, t.devices = [], {}
    assert scopes.reduce(t) is None


# ------------------------------------------------------------------ recorded

def test_small_trace_reduces_as_before_and_gives_its_offset():
    """`small.xplane.pb` (three runs of a jitted matmul, no scopes): the
    totals `tracereduce` gives, pinned, and the offset its three run_id
    pairs bound."""
    r = scopes.reduce_dir(DATA)
    plain = tracereduce.reduce_dir(DATA)
    for key in ("busy_s", "window_s", "device_ops", "programs"):
        assert r[key] == plain[key]
    assert r["busy_s"] == pytest.approx(2.3671e-05, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.035809018, rel=1e-9)
    assert dict(r["programs"]) == pytest.approx({"f": 2.3671e-05})
    lo, hi = r["clock_offset_s"]
    assert (round(lo * 1e3, 2), round(hi * 1e3, 2)) == (1.61, 2.20)
    assert r["scopes"]["seconds"] == pytest.approx({"other": 2.3671e-05})
    assert r["spans"] == {"bench.call": {"count": 3,
                                         "seconds": pytest.approx(0.00304724)}}


def test_scoped_trace_by_scope_span_and_gap(scoped_dir):
    """`scoped.xplane.pb.gz` (`record_scoped_trace.py`): two 4-die calls
    of the smoke-geometry chunk program, 2 chunks each, on a TPU v5e."""
    r = scopes.reduce_dir(scoped_dir)
    seconds = r["scopes"]["seconds"]
    assert seconds == pytest.approx({
        "sample": 1.43729e-04, "stem": 1.0092e-05, "s0b0": 3.81499e-04,
        "s0pool": 1.226e-06, "s1b0": 3.28239e-04, "s1pool": 4.28e-07,
        "head": 1.571e-06, "other": 2.157699e-03}, rel=1e-5)
    # the copies with no tf_op went forward; no op time is lost or doubled
    assert r["scopes"]["unscoped_raw_s"] == pytest.approx(7.0748e-05,
                                                          rel=1e-5)
    assert sum(seconds.values()) == pytest.approx(r["busy_s"])
    lo, hi = r["clock_offset_s"]
    assert (round(lo * 1e3, 4), round(hi * 1e3, 4)) == (1.1733, 1.2843)
    assert len(r["clock_segments"]) == 1
    assert {n: t["count"] for n, t in r["spans"].items()} == {
        "bench.run_mc_detector": 2, "repro.mc.planes": 2,
        "repro.mc.dispatch": 4, "repro.mc.wait": 4, "repro.mc.score": 4}
    # the device idles while the host scores each call's chunks
    assert [g[0] for g in r["idle_gaps"][:4]] == ["repro.mc.score"] * 4
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert max(r["idle_by_span"], key=r["idle_by_span"].get) \
        == "repro.mc.planes"
