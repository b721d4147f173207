"""The trace reduction on a trace written by hand whose answers are
known."""
import pytest

from chipbench import trace as tr

DEV = "/device:TPU:0"
# op events are named by their HLO instruction, as the chip's trace shows
KERNEL = ("%bitserial_matmul.1 = s32[512,128]{1,0:T(8,128)S(1)} custom-call("
          "u8[512,1280]{1,0} %pad.7, u8[1280,128]{1,0} %pad.6), "
          'custom_call_target="tpu_custom_call"')
READS_KERNEL = ("%slice.2 = s32[500,100]{1,0} slice(s32[512,128]{1,0} "
                "%bitserial_matmul.1), slice={[0:500], [0:100]}")


def _hand_trace():
    E = tr.Event
    return [
        E("/host:CPU", "python", "chipbench.window", 0, 1000),
        E("/host:CPU", "python", "chipbench.step", 0, 600),
        E("/host:CPU", "python", "chipbench.submit", 600, 100),
        # one adapter program: decode op, kernel, decode op
        E(DEV, tr.MODULES_LINE, "jit__pallas_exact(123)", 100, 200),
        E(DEV, tr.OPS_LINE, "fusion.1", 100, 50),
        E(DEV, tr.OPS_LINE, KERNEL, 150, 100),
        E(DEV, tr.OPS_LINE, READS_KERNEL, 260, 40),
        # an op of another program, partly outside the window
        E(DEV, tr.MODULES_LINE, "jit_other", 950, 100),
        E(DEV, tr.OPS_LINE, "copy.3", 950, 100),
    ]


def test_hand_trace_busy_kernel_decode_and_gaps():
    s = tr.summarize(_hand_trace())
    assert s.window_s == pytest.approx(1e-6)
    # busy: [100, 250] + [260, 300] + [950, 1000] clipped = 240 ns
    assert s.busy_s == pytest.approx(240e-9)
    assert s.kernel_s == pytest.approx(100e-9) and s.kernel_events == 1
    assert s.decode_s == pytest.approx(90e-9)
    assert s.devices == 1
    # an op that only reads the kernel's result is decode, not kernel
    assert s.top_ops[0] == ["%bitserial_matmul.1 = s32[512,128]",
                            pytest.approx(100e-9)]
    # gaps: [300, 950] under submit (mid 625), [0, 100] under step, ...
    assert s.idle_gaps[0] == ["chipbench.submit@0.000s",
                              pytest.approx(650e-9)]
    assert s.idle_gaps[1][0].startswith("chipbench.step@")
    assert s.idle_gaps[1][1] == pytest.approx(100e-9)
    assert sum(g for _, g in s.idle_gaps) == pytest.approx(1e-6 - 240e-9)


def test_a_trace_needs_one_window_span():
    events = [e for e in _hand_trace() if e.name != tr.WINDOW_SPAN]
    with pytest.raises(ValueError):
        tr.summarize(events)


def test_adapter_ops_with_no_kernel_event_raise():
    """A kernel renamed past ``KERNEL_OP`` would move its time into the
    decode's; the reduction refuses instead of reading it so."""
    events = [e if e.name != KERNEL
              else tr.Event(e.plane, e.line, "renamed_call", e.start_ns,
                            e.dur_ns) for e in _hand_trace()]
    with pytest.raises(ValueError, match="none matched the kernel"):
        tr.summarize(events)


# Extracts of traces recorded on one v5e chip ("TPU v5 lite") by
# ``--trace 1`` runs of each cell: the first 400 device events of the
# window (the stem's adapter programs), cut by ``inspect_trace.py``.
CHIP = {
    "inception_v3.b1": dict(events=403, kernel_events=22,
                            kernel_s=0.006489901, busy_s=0.00810883,
                            decode_s=0.001607148, window_s=0.088906151),
    "inception_v3_pruned50.b1": dict(events=403, kernel_events=19,
                                     kernel_s=0.004853269,
                                     busy_s=0.006131857,
                                     decode_s=0.001262175,
                                     window_s=0.339163798),
}


def _chip_events(cell):
    import pathlib

    path = pathlib.Path(__file__).parent / "data" / f"{cell}.chip_trace.json"
    return tr.load_extract(str(path))


@pytest.mark.parametrize("cell", sorted(CHIP))
def test_reduction_of_a_trace_recorded_on_the_chip(cell):
    events = _chip_events(cell)
    want = CHIP[cell]
    s = tr.summarize(events)
    assert len(events) == want["events"]
    for key in ("kernel_events", "kernel_s", "busy_s", "decode_s",
                "window_s"):
        assert getattr(s, key) == pytest.approx(want[key], rel=1e-9), key

    # the same numbers by a second route: a sweep over op edges for busy,
    # the kernel by its instruction's name, the decode by containment
    lo, hi = tr.window_of(events)
    ops = [e for e in events if e.line == tr.OPS_LINE]
    edges = sorted([(max(e.start_ns, lo), 1) for e in ops]
                   + [(min(e.end_ns, hi), -1) for e in ops])
    busy, depth, since = 0.0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    assert s.busy_s == pytest.approx(busy / 1e9, rel=1e-9)
    kernel = [e for e in ops if e.name.startswith("%bitserial_matmul")
              and "custom-call(" in e.name]
    assert s.kernel_events == len(kernel) > 0
    assert s.kernel_s == pytest.approx(sum(e.dur_ns for e in kernel) / 1e9)
    modules = [e for e in events if e.line == tr.MODULES_LINE
               and "_pallas_exact" in e.name]
    decode = sum(e.dur_ns for e in ops if e not in kernel and any(
        m.start_ns <= e.start_ns and e.end_ns <= m.end_ns for m in modules))
    assert s.decode_s == pytest.approx(decode / 1e9)

    # the kernel leads the device ops; every idle gap lies in a step
    assert s.top_ops[0][0].startswith("%bitserial_matmul")
    gaps = [g for _, g in s.idle_gaps]
    assert gaps == sorted(gaps, reverse=True) and min(gaps) > 0
    assert sum(gaps) <= s.window_s - s.busy_s + 1e-12
    assert all(label.startswith("chipbench.step@") for label, _ in s.idle_gaps)
