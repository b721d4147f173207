"""The harness finds every part of a cell by name, keeps to the naming
rules, refuses to run off a TPU, and decides ``correct`` by the
comparison with the plain reference: true for the program, false for the
control (the reference at 4 bits in the program's place) and for an
answer altered where it is produced.  Runs on the CPU at a tiny size."""
import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

from chipbench import loadgen, run
from chipbench.peaks import PEAKS

ROOT = pathlib.Path(run.__file__).resolve().parents[1]
BENCH = run.load_bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TINY = "chipbench/tests/data/inception_v3_tiny.json"


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_its_parts_by_name(workload):
    cell, config, traffic, e2e, per_layer = run.cell_parts(BENCH, workload)
    fam = run.family(config)
    for fn in ("make_params", "image_stream", "reference_logits",
               "conv_layers", "build_engine", "request"):
        assert callable(getattr(fam, fn))
    assert {m["name"] for m in e2e} >= {"setup_s"} and len(e2e) >= 2
    assert per_layer
    for m in e2e + per_layer:
        assert callable(run.metric_reader(m["name"]).read)
    for m in per_layer:
        assert m["moves"] in {e["name"] for e in e2e}
    assert set(config["limits"]) == {"logit_err"}
    assert loadgen.warm_batches(traffic)


def test_names_units_and_paths_keep_to_the_rules():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/")
        assert (ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert (ROOT / "chipbench" / "traffic"
                / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_off_a_tpu_it_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def _tiny_bench():
    bench = json.loads(json.dumps(BENCH))
    bench["configs"] = [dict(bench["configs"][0], name="tiny", file=TINY)]
    bench["workloads"] = [dict(bench["workloads"][0], name="tiny.b1",
                               config="tiny")]
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny.b1"]
    return bench


def _run_tiny(seed=2**31 + 11):
    return run.run_cell("tiny.b1", seed, 0.5, False, PEAKS["TPU v5 lite"],
                        _tiny_bench(), t0=0.0)


def test_program_is_correct_at_a_tiny_size():
    res = _run_tiny()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_err"]["value"] < 1e-5


def _replace_answers(monkeypatch, answer):
    from repro.launch.serve import NCServingEngine

    forward = NCServingEngine._forward

    def broken(self, x, schedule):
        logits, report = forward(self, x, schedule)
        return answer(np.array(logits), x), report

    monkeypatch.setattr(NCServingEngine, "_forward", broken)


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference at 4 bits, the precision below the configuration's
    8, answering in the program's place."""
    fam = run.family(json.loads((ROOT / TINY).read_text()))
    cfg = json.loads((ROOT / TINY).read_text())
    seed = 2**31 + 11
    import jax

    params = jax.tree.map(np.asarray, fam.make_params(cfg, seed))
    _replace_answers(monkeypatch, lambda logits, x: np.stack(
        [fam.reference_logits(cfg, params, img, bits=4) for img in x]))
    res = _run_tiny(seed)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > 3 * (
        res["checks"]["logit_err"]["limit"])


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    def alter(logits, x):
        logits[:, 0] += 0.5 * logits.std()
        return logits

    _replace_answers(monkeypatch, alter)
    res = _run_tiny()
    assert not res["correct"]


def test_a_batch_off_the_recovery_ladder_is_not_correct(monkeypatch):
    """The window's first batch fails on the primary schedule and its
    retry, and is served by the dense fallback schedule: the request is
    answered, with the right logits, yet off the path the cell times."""
    from repro.launch.serve import NCServingEngine

    forward = NCServingEngine._forward
    calls = {"n": 0}

    def flaky(self, x, schedule):
        calls["n"] += 1
        if calls["n"] in (2, 3):  # after the warm-up's one batch
            raise RuntimeError("planted fault")
        return forward(self, x, schedule)

    monkeypatch.setattr(NCServingEngine, "_forward", flaky)
    res = _run_tiny()
    assert not res["correct"]
    assert res["failed"] >= 1
    assert res["checks"]["degraded_batches"]["value"] >= 1


class _Req:
    def __init__(self, rid):
        self.rid, self.done, self.failed = rid, False, False


class _Engine:
    """Serves every queued request in one step of ``step_s`` seconds."""

    def __init__(self, step_s=0.02):
        self.queue, self.step_s, self.batches = [], step_s, []

    def submit(self, r):
        self.queue.append(r)

    def step(self, flush=False):
        import time

        if not self.queue:
            return False
        time.sleep(self.step_s)
        self.batches.append(len(self.queue))
        for r in self.queue:
            r.done = True
        self.queue = []
        return True


def test_closed_loop_closes_the_window_after_the_request_in_flight():
    win = loadgen.run(_Engine(), {"arrivals": "closed", "clients": 1},
                      lambda rid, img: _Req(rid), iter(range(100)), 0.05,
                      [], seed=1)
    assert len(win.ok) == 3  # sent at 0, 0.02, 0.04; none after 0.05
    assert win.window_s >= 0.06


def test_open_loop_arrives_at_its_rate_and_counts_the_wait():
    eng = _Engine(step_s=0.05)
    traffic = {"arrivals": "open", "rate_per_s": 100.0, "max_batch": 8}
    win = loadgen.run(eng, traffic, lambda rid, img: _Req(rid),
                      iter(range(10_000)), 0.5, [], seed=2**31 + 3)
    assert 30 <= len(win.ok) <= 80  # about 50 arrivals in 0.5 s
    assert max(eng.batches) > 1  # arrivals queue while a step runs
    # a request that arrived during a step waits for it and the next
    assert max(s.latency_s for s in win.ok) >= 0.05


@pytest.mark.parametrize("bursts", [None, {"period_s": 1.0, "high": 2.5,
                                            "low": 0.3}])
def test_every_seed_draws_the_same_gaps_in_its_own_order(bursts):
    import itertools

    traffic = {"arrivals": "open", "rate_per_s": 10.0, "bursts": bursts}
    a = list(itertools.islice(loadgen.arrival_gaps(1), loadgen.GAPS))
    b = list(itertools.islice(loadgen.arrival_gaps(2**31 + 9), loadgen.GAPS))
    assert a != b and sorted(a) == sorted(b)
    assert np.mean(a) == pytest.approx(1.0, rel=2e-3)
    t = list(itertools.islice(loadgen.arrival_times(traffic, 5), 2000))
    assert t[0] == 0.0 and all(y > x for x, y in zip(t, t[1:]))
    if bursts:  # about 2.5 x 10 arrivals in the first second
        assert 15 <= sum(x < 1.0 for x in t) <= 35


@pytest.mark.parametrize("traffic,sizes", [
    ({"arrivals": "closed", "clients": 1, "max_batch": 1}, [1]),
    ({"arrivals": "closed", "clients": 4, "max_batch": 4}, [4]),
    ({"arrivals": "closed", "clients": 6, "max_batch": 4}, [2, 4]),
    ({"arrivals": "open", "rate_per_s": 1.0, "max_batch": 3}, [1, 2, 3]),
])
def test_warm_up_covers_every_batch_size_the_window_runs(traffic, sizes):
    loadgen.check(traffic)
    assert loadgen.warm_batches(traffic) == sizes


def test_a_traffic_file_of_unknown_arrivals_is_refused():
    with pytest.raises(ValueError):
        loadgen.check({"arrivals": "poisson", "max_batch": 1})
