"""The reduction of the program's spans (``program_spans.py``) on a trace
written by hand whose answers are known, and the readers of the metrics
that read it."""
import types

import pytest

from chipbench import program_spans as ps
from chipbench import run
from chipbench import trace as tr

DEV = "/device:TPU:0"
KERNEL = ("%bitserial_matmul.1 = s32[512,128]{1,0} custom-call("
          "u8[512,1280]{1,0} %pad.7, u8[1280,128]{1,0} %pad.6)")
T = "/host:CPU/python"


def _spans():
    S = ps.Span
    return [
        S(T, "nc.conv.pack", -100, 108),  # opens before the window
        S(T, "nc.serve.step", 10, 980, {"batch": 2, "request_ids": "0 1"}),
        S(T, "nc.forward", 20, 960),
        S(T, "nc.layer", 30, 470, {"layer": "Conv2d_1a_3x3"}),
        S(T, "nc.conv.im2col", 30, 70),
        S(T, "nc.conv.pack", 100, 50),
        S(T, "nc.pallas.launch", 150, 50),
        S(T, "nc.pallas.wait", 200, 200),
        S(T, "nc.pallas.scatter", 400, 20),
        S(T, "nc.conv.store", 420, 10),
        S(T, "nc.conv.epilogue", 440, 40),
        S(T, "nc.accounting", 480, 20),
        S(T, "nc.layer", 500, 200, {"layer": "MaxPool_3a_3x3"}),
        S(T, "nc.pool", 510, 180),
        S(T, "nc.concat", 700, 60),
        S(T, "nc.pool", 995, 105),  # closes after the window
    ]


def _events():
    E = tr.Event
    return [
        E("/host:CPU", "python", tr.WINDOW_SPAN, 0, 1000),
        E(DEV, tr.OPS_LINE, KERNEL, 210, 90),
        E(DEV, tr.OPS_LINE, "fusion.1", 300, 50),
        E(DEV, tr.OPS_LINE, "copy.3", 950, 100),  # partly outside
    ]


def test_self_time_subtracts_children():
    s = ps.summarize(_spans(), _events())
    assert s.self_ns["nc.serve.step"] == 980 - 960
    assert s.self_ns["nc.forward"] == 960 - (470 + 200 + 60)
    assert s.self_ns["nc.layer"] == (470 - 460) + (200 - 180)
    assert s.self_ns["nc.pallas.wait"] == 200  # a leaf keeps its length
    assert s.stage_ns("scatter") == 20 + 10
    assert s.stage_ns("requant") == 40 + 60


def test_spans_are_clipped_to_the_window():
    s = ps.summarize(_spans(), _events())
    assert s.self_ns["nc.conv.pack"] == 8 + 50
    assert s.self_ns["nc.pool"] == 180 + 5
    assert s.counts["nc.pool"] == 2 and s.images == 2


def test_idle_charged_to_stages_and_outside_them_is_the_windows_idle():
    s = ps.summarize(_spans(), _events())
    busy = (350 - 210) + (1000 - 950)
    assert s.idle_total_ns == 1000 - busy
    assert sum(s.idle_ns.values()) + s.idle_outside_ns == s.idle_total_ns
    # the wait is idle before the kernel starts and after the decode ends
    assert s.idle_ns["nc.pallas.wait"] == 10 + 50
    assert s.idle_ns["nc.pool"] == 180  # the tail pool runs under copy.3
    # uncovered: [8, 30], [430, 440], [500, 510], [690, 700], [760, 950]
    assert s.idle_outside_ns == 22 + 10 + 10 + 10 + 190
    assert s.layers["Conv2d_1a_3x3"] == {"calls": 1, "host_ns": 470,
                                         "kernel_ns": 90, "kernels": 1}
    assert s.layers["MaxPool_3a_3x3"]["kernels"] == 0


def _run(monkeypatch, file_window, spans):
    monkeypatch.setattr(ps, "_CACHE", {})
    monkeypatch.setattr(ps, "trace_file", lambda: "run.xplane.pb")
    monkeypatch.setattr(ps, "load", lambda path: (file_window, spans))
    return types.SimpleNamespace(events=_events(), images=2)


@pytest.mark.parametrize("window", [(0.0, 1001.0), (1.0, 1000.0), None])
def test_a_trace_whose_window_differs_from_the_runs_is_refused(
        monkeypatch, window):
    assert ps.summary(_run(monkeypatch, window, _spans())) is None
    assert ps.summary(_run(monkeypatch, (0.0, 1000.0), _spans())) is not None


NEW = ["host_im2col_ms_per_image", "host_pack_ms_per_image",
       "host_launch_ms_per_image", "device_wait_ms_per_image",
       "host_scatter_ms_per_image", "host_requant_ms_per_image",
       "host_pool_ms_per_image", "host_accounting_ms_per_image",
       "idle_outside_stages_ms_per_image"]


def test_readers_give_ms_per_image(monkeypatch):
    r = _run(monkeypatch, (0.0, 1000.0), _spans())
    got = {m: run.metric_reader(m).read(r) for m in NEW}
    ns = {"host_im2col_ms_per_image": 70, "host_pack_ms_per_image": 58,
          "host_launch_ms_per_image": 50, "device_wait_ms_per_image": 200,
          "host_scatter_ms_per_image": 30, "host_requant_ms_per_image": 100,
          "host_pool_ms_per_image": 185, "host_accounting_ms_per_image": 20,
          "idle_outside_stages_ms_per_image": 242}
    assert got == {m: pytest.approx(v / 1e6 / 2) for m, v in ns.items()}


def test_readers_give_nothing_for_a_program_without_spans(monkeypatch):
    from repro.core import backends

    backends.dispatch_stats_clear()
    r = _run(monkeypatch, (0.0, 1000.0), [])
    for m in NEW + ["adapter_bytes_per_image"]:
        assert run.metric_reader(m).read(r) is None, m
    untraced = types.SimpleNamespace(events=None, images=2)
    assert run.metric_reader(NEW[0]).read(untraced) is None


# Extracts of traces recorded on one v5e chip ("TPU v5 lite") by
# ``--trace 1`` runs of each cell with the program's spans: the first 400
# device events of the window (the stem) and the spans over the same
# stretch, stats kept, cut by ``program_spans.py --extract``.  Stage
# totals in ns; ``None`` where no span of the stage falls in the stretch.
CHIP = {
    "inception_v3.b1": dict(
        spans=118, layers={"Conv2d_1a_3x3": 22},
        stages={"im2col": 21139440, "pack": 31257167, "launch": 15006202,
                "wait": 38709708, "scatter": 2584207, "requant": None,
                "pool": None, "accounting": None},
        idle=104146737, outside=3277129),
    "inception_v3_pruned50.b1": dict(
        spans=107, layers={"Conv2d_1a_3x3": 16, "Conv2d_2a_3x3": 3},
        stages={"im2col": 167119199, "pack": 73574480, "launch": 13854867,
                "wait": 33513879, "scatter": 2417787, "requant": 132952831,
                "pool": None, "accounting": 1584969},
        idle=429945580, outside=10670863),
}


def _sweep_busy(ops, lo, hi):
    """Busy time in ``[lo, hi]`` by a sweep over the ops' clipped edges."""
    edges = sorted([(max(e.start_ns, lo), 1) for e in ops
                    if e.end_ns > lo and e.start_ns < hi]
                   + [(min(e.end_ns, hi), -1) for e in ops
                      if e.end_ns > lo and e.start_ns < hi])
    busy, depth, since = 0.0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


@pytest.mark.parametrize("cell", sorted(CHIP))
def test_span_reduction_of_a_trace_recorded_on_the_chip(cell):
    import pathlib

    path = pathlib.Path(__file__).parent / "data" / f"{cell}.chip_spans.json"
    events, spans = ps.load_extract(str(path))
    want = CHIP[cell]
    s = ps.summarize(spans, events)
    assert len(spans) == want["spans"]
    for stage, ns in want["stages"].items():
        assert s.stage_ns(stage) == ns, stage
    assert s.idle_total_ns == want["idle"]
    assert s.idle_outside_ns == want["outside"]

    # the same numbers by a second route: stage spans are leaves, so a
    # stage's self time is its spans' clipped length, and the idle charged
    # to one span is its length less the busy time of the ops inside it
    lo, hi = tr.window_of(events)
    ops = [e for e in events if e.line == tr.OPS_LINE]
    inside = [sp for sp in spans if sp.end_ns > lo and sp.start_ns < hi]
    stages = [sp for sp in inside if sp.name in ps.STAGE_SPANS]
    for sp in stages:
        assert not any(o is not sp and o.thread == sp.thread
                       and sp.start_ns <= o.start_ns < sp.end_ns
                       for o in inside)
    charged = 0.0
    for stage, names in ps.STAGES.items():
        mine = [sp for sp in stages if sp.name in names]
        length = sum(min(sp.end_ns, hi) - max(sp.start_ns, lo)
                     for sp in mine)
        assert (s.stage_ns(stage) or 0) == pytest.approx(length, abs=1e-3)
        for sp in mine:
            a, b = max(sp.start_ns, lo), min(sp.end_ns, hi)
            charged += (b - a) - _sweep_busy(ops, a, b)
    idle = (hi - lo) - _sweep_busy(ops, lo, hi)
    assert s.idle_total_ns == pytest.approx(idle, abs=1e-3)
    assert s.idle_outside_ns == pytest.approx(idle - charged, abs=1e-3)
    assert s.idle_outside_ns < 0.1 * s.idle_total_ns

    # per layer: the kernel's instances that start inside its span
    kernel = [e for e in ops if e.name.startswith("%bitserial_matmul")
              and "custom-call(" in e.name]
    for layer, n in want["layers"].items():
        [sp] = [sp for sp in spans if sp.stats.get("layer") == layer]
        got = [e for e in kernel if sp.start_ns <= e.start_ns < sp.end_ns]
        assert len(got) == s.layers[layer]["kernels"] == n
        assert s.layers[layer]["kernel_ns"] == pytest.approx(
            sum(e.dur_ns for e in got))
