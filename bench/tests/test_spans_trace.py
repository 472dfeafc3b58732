"""The reading of the program's spans and attention scope, checked by
hand on known events, on a small trace recorded on a v5e
(``data/trace_spans.json``: the first 200 ms of a traced window of
``qwen3-14b-11l.offline-decode``, as ``benchlib.spans.extract`` read it,
ops by short name with their scope flag), and end to end on the CPU
through ``bench/trace_spans.py`` at a toy size.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import drive, readings, spans, spec, trace  # noqa: E402

DATA = BENCH / "tests" / "data" / "trace_spans.json"
TINY = spec.load_json(BENCH / "tests" / "tiny.json")
MS = 1_000_000


def by_hand_events():
    """Two rounds in a 100 ms window. Round 1's program ([12, 40)) holds
    a scoped fusion inside a ``while``; a first-token sample runs during
    the harvest ([43, 44)); round 2's program ([72, 90)) holds one
    scoped and one unscoped op."""
    return {
        "host": [("window", 0, 100 * MS),
                 ("round", 9 * MS, 52 * MS), ("round", 69 * MS, 27 * MS),
                 ("serve.step", 10 * MS, 50 * MS),
                 ("serve.admit", 10 * MS, 2 * MS),
                 ("serve.plan", 10 * MS + MS // 2, MS),
                 ("serve.wait", 14 * MS, 27 * MS),
                 ("serve.harvest", 41 * MS, 7 * MS),
                 ("serve.retire", 48 * MS, 8 * MS),
                 ("frontend.pump", 60 * MS, 4 * MS),
                 ("hook", 64 * MS, 4 * MS),
                 ("serve.step", 70 * MS, 25 * MS),
                 ("serve.wait", 73 * MS, 18 * MS)],
        "modules": [("jit__bucket_round_impl(1)", 12 * MS, 28 * MS),
                    ("jit_argmax(2)", 43 * MS, MS),
                    ("jit__bucket_round_impl(1)", 72 * MS, 18 * MS)],
        "ops": [("%while.1 = (s32[]) while(...)", 12 * MS, 28 * MS, False),
                ("%fusion.1 = bf16[4,8]{1,0} fusion(...)", 12 * MS, 18 * MS,
                 True),
                ("%fusion.2 = bf16[4]{0} fusion(...)", 30 * MS, 10 * MS,
                 False),
                ("%argmax.1 = s32[1]{0} reduce(...)", 43 * MS, MS, False),
                ("%fusion.3 = bf16[4,8]{1,0} fusion(...)", 72 * MS, 10 * MS,
                 True),
                ("%fusion.4 = bf16[4]{0} fusion(...)", 82 * MS, 8 * MS,
                 False)],
    }


def test_reduce_by_hand():
    r = spans.reduce(by_hand_events())
    assert r["steps"] == [pytest.approx([0.050, 0.027, 0.001]),
                          pytest.approx([0.025, 0.018, 0.0])]
    assert r["step_gaps_s"] == [pytest.approx(0.010)]
    # the while's self time is 0; fusion.1 and fusion.3 are scoped
    assert r["scoped_device_s"] == pytest.approx(0.028)
    # idle [0,12) [40,43) [44,72) [90,100), split over the innermost
    # span: program spans first, the benchmark's next, else none
    want = {"none": 14, "round": 3, "serve.admit": 1, "serve.plan": 1,
            "serve.wait": 2, "serve.harvest": 6, "serve.retire": 8,
            "serve.step": 10, "frontend.pump": 4, "hook": 4}
    assert r["idle_in_span"] == {k: pytest.approx(v * 1e-3)
                                 for k, v in want.items()}
    assert sum(r["idle_in_span"].values()) == pytest.approx(0.053)
    # a gap is named by the innermost span at its middle: [40,43) lies
    # mostly in the harvest; [44,72) spans retire -> pump, middle 58 in
    # the step; [90,100) at 95 only in the benchmark's round
    assert r["idle_gaps"] == [["serve.step", pytest.approx(0.028)],
                              ["none", pytest.approx(0.012)],
                              ["round", pytest.approx(0.010)],
                              ["serve.harvest", pytest.approx(0.003)]]


def test_readers_by_hand():
    ev = by_hand_events()
    red = trace.reduce({k: [e[:3] for e in v] for k, v in ev.items()})
    red.update(spans.reduce(ev))
    rd = drive.Round(t=0.5, out_tokens=32, first_tokens=0, prefill_tokens=0,
                     ctx_decoding=16 * 800, n_decoding=16)
    win = types.SimpleNamespace(rounds=[rd], trace_t0=0.0, trace_t1=1.0)
    run = readings.Run(config=TINY, window=win, setup_s=0.0,
                       peaks={"hbm_bytes_per_s": 819e9}, trace=red)

    def read(name):
        return spec.metric_reader(name).read(run)
    assert read("round_host_ms.offline") == pytest.approx((23 + 7) / 2)
    assert read("plan_ms.offline") == pytest.approx(0.5)
    assert read("round_gap_ms.offline") == pytest.approx(10.0)
    # scoped 28 ms of the round programs' 46 ms
    assert read("attention_share.offline") == pytest.approx(100 * 28 / 46)
    # 32 decode tokens x 800 positions x K and V of 2 layers x 2 kv heads
    # x 16 dims x 2 bytes, at 819 GB/s, over 28 ms
    need = 32 * 800 * (2 * 2 * 2 * 16 * 2) / 819e9
    assert read("attention_roofline.offline") == pytest.approx(
        100 * need / 0.028)


def test_readers_find_nothing_without_program_spans():
    """A program that predates the spans: every reader returns None."""
    ev = by_hand_events()
    ev["host"] = [e for e in ev["host"] if e[0] not in spans.PROGRAM_SPANS]
    ev["ops"] = [e[:3] + (False,) for e in ev["ops"]]
    red = trace.reduce({k: [e[:3] for e in v] for k, v in ev.items()})
    red.update(spans.reduce(ev))
    win = types.SimpleNamespace(rounds=[], trace_t0=0.0, trace_t1=1.0,
                                stats_open={}, stats_close={})
    run = readings.Run(config=TINY, window=win, setup_s=0.0,
                       peaks={"hbm_bytes_per_s": 819e9}, trace=red)
    for name in ("round_host_ms.offline", "plan_ms.offline",
                 "round_gap_ms.offline", "attention_share.offline",
                 "attention_roofline.offline", "lane_use_share.offline"):
        assert spec.metric_reader(name).read(run) is None, name


def test_reduce_recorded_v5e_trace():
    ev = {k: [tuple(e) for e in v]
          for k, v in json.loads(DATA.read_text()).items()}
    r = spans.reduce(ev)
    base = trace.reduce({k: [e[:3] for e in v] for k, v in ev.items()})
    # the idle time is split exactly: its parts add up to window - busy
    assert sum(r["idle_in_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-9)
    # every program span of the recording is found, and most of the
    # idle time lies in one of them
    names = {e[0] for e in ev["host"]}
    assert {"serve.step", "serve.wait", "serve.plan",
            "frontend.pump"} <= names
    prog = sum(v for k, v in r["idle_in_span"].items()
               if k in spans.PROGRAM_SPANS)
    assert prog > 0.5 * sum(r["idle_in_span"].values())
    # one entry per serve.step that lies whole in the window, each with
    # a wait and a plan shorter than itself
    win = [e for e in ev["host"] if e[0] == "window"][0]
    lo, hi = win[1], win[1] + win[2]
    whole = [e for e in ev["host"] if e[0] == "serve.step"
             and lo <= e[1] and e[1] + e[2] <= hi]
    assert len(r["steps"]) == len(whole) >= 1
    assert all(0 < w < d and 0 <= p < d for d, w, p in r["steps"])
    # by hand: no scoped op holds another op that takes time, so the
    # scoped self time is the sum of their durations within the window
    ops = sorted(e for e in ev["ops"] if lo <= e[1] < hi)
    scoped = [e for e in ops if e[3]]
    assert scoped
    for n, s, d, _ in scoped:
        assert not any(s <= o[1] and o[1] + o[2] <= s + d and 0 < o[2] < d
                       for o in ops if o[0] != n)
    assert r["scoped_device_s"] == pytest.approx(
        sum(min(d, hi - s) for _, s, d, _ in scoped) * 1e-9)


def test_trace_spans_tool_on_cpu(tmp_path):
    """The whole tool at a toy size on the CPU: the program's spans are
    in the kept trace and the lane counters read; the CPU trace has no
    device plane, so the device readers find nothing."""
    import run as bench_run
    import trace_spans
    from test_faults import MIX
    keep = str(tmp_path / "keep")
    out, extra = bench_run.run_cell(
        "qwen3-14b-11l.offline-decode", 7, 2.0, True, require_tpu=False,
        config=TINY, mix=MIX, keep_trace=keep)
    got = trace_spans.read(out, extra, keep, TINY)
    assert got["program_spans"] == sorted(spans.PROGRAM_SPANS)
    assert 0 < got["metrics"]["lane_use_share.offline"] <= 100
    assert got["metrics"]["attention_share.offline"] is None


def test_scope_read_from_op_metadata(tmp_path):
    """A v5e trace keeps an op's scope path in the ``tf_op`` stat of the
    op's event metadata (as a string, or as a reference to a stat
    metadata's name). An XSpace written with the profiler's own protocol
    buffers, read back through ``spans.extract``."""
    xp = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xp.XSpace()
    plane = space.planes.add(id=1, name="/device:TPU:0")
    plane.stat_metadata[1].name = spans.SCOPE_STAT
    plane.stat_metadata[2].name = "jit(f)/while/body/paged_attention/mul:"
    plane.stat_metadata[3].name = "hlo_category"
    ops = {1: ("%fusion.1 = bf16[4]{0} fusion(...)",
               "jit(f)/while/body/paged_attention/dot_general:", None),
           2: ("%fusion.2 = bf16[4]{0} fusion(...)", None, 2),
           3: ("%fusion.3 = bf16[4]{0} fusion(...)",
               "jit(f)/while/body/mlp/dot_general:", None),
           4: ("%fusion.4 = bf16[4]{0} fusion(...)",
               "jit(f)/paged_attention_x/add:", None)}
    for i, (name, path, ref) in ops.items():
        md = plane.event_metadata[i]
        md.id, md.name = i, name
        md.stats.add(metadata_id=3, str_value="loop fusion")
        if path is not None:
            md.stats.add(metadata_id=1, str_value=path)
        if ref is not None:
            md.stats.add(metadata_id=1, ref_value=ref)
    line = plane.lines.add(id=1, name=trace.OPS_LINE, timestamp_ns=1000)
    for i in ops:
        line.events.add(metadata_id=i, offset_ps=i * 10_000_000,
                        duration_ps=5_000_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert spans.scoped_names(str(path)) == {ops[1][0], ops[2][0]}
    ev = spans.extract(str(path))
    assert [(n, f) for n, _, _, f in ev["ops"]] == [
        (ops[1][0], True), (ops[2][0], True), (ops[3][0], False),
        (ops[4][0], False)]
