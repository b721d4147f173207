"""The cells ``resnet50_v1_5.b1`` and ``inception_v3.b4``: their parts
resolve by name, the ResNet family's structure copy is the program's
network, the program is correct at a tiny size and the 4-bit control in
its place is not, and the readers of the residual join's metrics read
the program's ``nc.residual`` spans.  Runs on the CPU."""
import json
import pathlib
import types

import numpy as np
import pytest

from chipbench import loadgen, run, work
from chipbench import program_spans as ps
from chipbench.peaks import PEAKS

ROOT = pathlib.Path(run.__file__).resolve().parents[1]
BENCH = run.load_bench()
TINY = "chipbench/tests/data/resnet50_tiny.json"
SEED = 2**31 + 11


def test_closed_b4_loads_and_both_cells_resolve():
    _, _, traffic, _, _ = run.cell_parts(BENCH, "inception_v3.b4")
    assert loadgen.warm_batches(traffic) == [4]
    cell, config, traffic, e2e, per_layer = run.cell_parts(
        BENCH, "resnet50_v1_5.b1")
    assert (cell["chips"], config["family"], traffic["max_batch"]) == (
        1, "resnet50", 1)
    assert {"host_residual_ms_per_image", "residual_joins_per_image",
            "passes_per_kernel_call", "kernel_calls_per_image",
            "bitserial_kernel_roofline", "mfu"} <= {
                m["name"] for m in per_layer}


def test_the_family_copy_is_the_programs_network():
    """The benchmark's own structure (for the work and the reference)
    names and shapes every conv as the program's spec list does, and the
    work per image is ResNet-50's."""
    from repro.models import resnet

    config = run.cell_parts(BENCH, "resnet50_v1_5.b1")[1]
    fam = run.family(config)
    layers = fam.conv_layers(config)
    specs = [s for s in resnet.resnet50_specs(fam.program_config(config))
             if s.kind in ("conv", "fc")]
    assert [(l["name"], l["H"], l["C"], l["R"], l["S"], l["M"], l["E"])
            for l in layers] == [(s.name, s.H, s.C, s.R, s.S, s.M, s.E)
                                 for s in specs]
    assert work.network_work(layers) == {
        "macs": 4_089_184_256, "ops": 8_178_368_512, "bytes": 47_282_344}


def _tiny_bench():
    bench = json.loads(json.dumps(BENCH))
    bench["configs"] = [dict(bench["configs"][-1], name="tiny", file=TINY)]
    bench["workloads"] = [dict(w, name="tiny.b1", config="tiny")
                          for w in bench["workloads"]
                          if w["name"] == "resnet50_v1_5.b1"]
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny.b1"]
    return bench


def _run_tiny():
    return run.run_cell("tiny.b1", SEED, 0.5, False, PEAKS["TPU v5 lite"],
                        _tiny_bench(), t0=0.0)


def test_program_is_correct_at_a_tiny_size():
    res = _run_tiny()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["logit_err"]["value"] < 1e-5


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference at 4 bits, the precision below the configuration's
    8, answering in the program's place."""
    import jax
    from repro.launch.serve import NCServingEngine

    cfg = json.loads((ROOT / TINY).read_text())
    fam = run.family(cfg)
    params = jax.tree.map(np.asarray, fam.make_params(cfg, SEED))
    forward = NCServingEngine._forward

    def control(self, x, schedule):
        _, report = forward(self, x, schedule)
        return np.stack([fam.reference_logits(cfg, params, img, bits=4)
                         for img in x]), report

    monkeypatch.setattr(NCServingEngine, "_forward", control)
    res = _run_tiny()
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > 3 * (
        res["checks"]["logit_err"]["limit"])


T = "/host:CPU/python"


def _run(monkeypatch, spans):
    from chipbench import trace as tr

    monkeypatch.setattr(ps, "_CACHE", {})
    monkeypatch.setattr(ps, "trace_file", lambda: "run.xplane.pb")
    monkeypatch.setattr(ps, "load", lambda path: ((0.0, 1000.0), spans))
    events = [tr.Event("/host:CPU", "python", tr.WINDOW_SPAN, 0, 1000)]
    return types.SimpleNamespace(events=events, images=2)


def test_residual_readers_read_the_join_spans(monkeypatch):
    S = ps.Span
    spans = [S(T, "nc.serve.step", 10, 900, {"batch": 2}),
             S(T, "nc.layer", 100, 300, {"layer": "block1_unit1_add"}),
             S(T, "nc.residual", 110, 250, {"layer": "block1_unit1_add"}),
             S(T, "nc.accounting", 360, 20),
             S(T, "nc.layer", 500, 100, {"layer": "block1_unit2_add"}),
             S(T, "nc.residual", 500, 90, {"layer": "block1_unit2_add"}),
             S(T, "nc.residual", 1100, 90)]  # after the window
    r = _run(monkeypatch, spans)
    assert run.metric_reader("host_residual_ms_per_image").read(r) == (
        pytest.approx((250 + 90) / 1e6 / 2))
    assert run.metric_reader("residual_joins_per_image").read(r) == 1.0


@pytest.mark.parametrize("metric", ["host_residual_ms_per_image",
                                    "residual_joins_per_image"])
def test_residual_readers_give_nothing_without_the_span(monkeypatch, metric):
    S = ps.Span
    r = _run(monkeypatch, [S(T, "nc.serve.step", 10, 900, {"batch": 1}),
                           S(T, "nc.pool", 20, 30)])
    assert run.metric_reader(metric).read(r) is None
    untraced = types.SimpleNamespace(events=None, images=2)
    assert run.metric_reader(metric).read(untraced) is None
