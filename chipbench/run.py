"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``workloads`` in
``BENCHMARK.json``) names a configuration (``configs/<config>.json``,
whose ``family`` is a module under ``families/``) and a traffic mix
(``traffic/<mix>.json``, parameters that the one generator in
``loadgen.py`` reads); each metric is a reader in
``metrics/<metric>.py``, whose name, unit and layer are those of its
entry in ``BENCHMARK.json``.  A new cell, configuration, mix or metric is
new files and entries only.

The run checks for the chips the cell asks for and exits non-zero with no
result when JAX finds fewer, or no TPU.  It then builds the weights from
the seed on the device, builds the system under test, serves one warm-up
batch of every size the mix runs (set-up ends there), and measures one
window of ``--seconds``.
With ``--trace 1`` the window is traced by the profiler and the result
carries the per-layer metrics; otherwise the end-to-end ones.  After the
window, every answered request is compared with the plain reference; a
request served off the primary path (failed, or by a degraded rung of
the engine's recovery ladder) or a kernel call that fell back off the
``pallas`` path makes the run not correct.
The last line of standard output is the result, one JSON object; the
numbers compared, with their limits, are the last lines of standard
error and the last key of the result.
"""
from __future__ import annotations

T0 = __import__("time").perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
# run as a script, the benchmark's directory must not shadow top-level
# modules (``trace``, ``stats``): import its files as ``chipbench.*``
sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve() != HERE]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import loadgen, trace, work  # noqa: E402
from chipbench.peaks import Peaks, peaks_for  # noqa: E402

OUT = ROOT / ".chipbench"  # traces; listed in .gitignore


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's parts by name
# ---------------------------------------------------------------------------
def load_bench(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell_parts(bench: dict, workload: str, root: pathlib.Path = ROOT):
    """``(cell, config, traffic, end_to_end, per_layer)`` of a workload:
    the configuration and traffic files read, and the metric entries
    that this cell reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(root / entry["file"]) as f:
        config = json.load(f)
    with open(HERE / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    loadgen.check(traffic)

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return (cell, config, traffic, mine(bench["end_to_end"]),
            mine(bench["per_layer"]))


def metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(config: dict):
    return importlib.import_module(f"chipbench.families.{config['family']}")


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What the metric readers read: the window's requests and spans,
    the program's counters, the work and peaks, and, in a traced run,
    the trace's events (``events``) with the reduction of ``trace.py``
    (``trace``), so that a reader may reduce the events its own way."""

    images: int
    window_s: float
    latencies_s: list
    overheads_s: list
    forward_s: float
    kernel_calls: int
    setup_s: float
    work: dict
    peaks: Peaks
    trace: trace.TraceSummary | None
    events: list | None


def compare(fam, config: dict, params_host: dict, answers: list,
            bits: int = 8) -> dict:
    """The number compared, over ``answers``, a list of (image, served
    logits): ``logit_err``, the widest gap between served and reference
    logits, in units of the reference logits' standard deviation over
    the classes."""
    import numpy as np

    err = 0.0
    for image, logits in answers:
        ref = fam.reference_logits(config, params_host, image, bits=bits)
        got = np.asarray(logits, np.float64)
        err = max(err, float(np.abs(got - ref).max()) / float(ref.std()))
    return {"logit_err": err}


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             peaks: Peaks, bench: dict | None = None,
             t0: float | None = None) -> dict:
    """Set up, warm up, measure and check one cell; returns the result
    object.  The caller has checked the devices."""
    import jax
    import numpy as np

    from repro.core import backends

    t0 = T0 if t0 is None else t0
    bench = load_bench() if bench is None else bench
    _, config, traffic, e2e, per_layer = cell_parts(bench, workload)
    fam = family(config)

    compiles = {"n": 0}

    def on_compile(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    params = jax.block_until_ready(fam.make_params(config, seed))
    engine = fam.build_engine(config, params, int(traffic["max_batch"]),
                              **traffic.get("engine", {}))
    walls = loadgen.time_forward(engine)
    span = jax.profiler.TraceAnnotation

    # warm-up: one batch of each size the window runs, every shape it uses
    warm = fam.image_stream(config, seed, stream=0)
    for n in loadgen.warm_batches(traffic):
        loadgen.serve_batch(engine, fam.request, warm, n, walls)
    degraded_setup = engine.degraded_batches
    compiles_setup = compiles["n"]
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s, {compiles_setup} compiles")

    backends.dispatch_stats_clear()
    walls.clear()
    compiles["n"] = 0
    trace_dir = OUT / "trace" / workload
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with span(trace.WINDOW_SPAN):
            win = loadgen.run(
                engine, traffic, fam.request,
                fam.image_stream(config, seed, stream=1), seconds, walls,
                seed, span=span)
    finally:
        if traced:
            jax.profiler.stop_trace()
    log(f"window {win.window_s:.3f} s, {len(win.served)} requests, "
        f"{compiles['n']} compiles inside the window")
    log("requests in completion order (latency s, forward s): "
        + ", ".join(f"({s.latency_s}, {s.forward_s})" for s in win.served))
    dispatch = backends.dispatch_stats()
    log(f"dispatch {dispatch}")
    ok = win.ok
    degraded = engine.degraded_batches - degraded_setup
    fallback = sum(d.get("fallback", 0) for d in dispatch.values())
    if engine.reports:
        rep = engine.reports[-1]
        log(f"modeled Neural Cache (simulated, not a metric): "
            f"{rep.total_modeled_cycles / rep.batch:.0f} cycles, "
            f"{rep.total_modeled_s / rep.batch * 1e3:.3f} ms per image")

    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    summary = events = None
    if traced:
        files = sorted(trace_dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{trace_dir}")
        events = trace.load_events(str(files[-1]))
        summary = trace.summarize(events)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    run = Run(images=len(ok), window_s=win.window_s,
              latencies_s=[s.latency_s for s in ok],
              overheads_s=[s.latency_s - s.forward_s for s in ok],
              forward_s=sum(s.forward_s for s in ok),
              kernel_calls=dispatch.get("pallas", {}).get("native", 0),
              setup_s=setup_s, work=work.network_work(fam.conv_layers(config)),
              peaks=peaks, trace=summary, events=events)
    metrics = {}
    for m in (per_layer if traced else e2e):
        value = metric_reader(m["name"]).read(run) if ok else None
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # the reference runs once the window is over and the program is gone
    params_host = jax.tree.map(np.asarray, params)
    del engine, params
    gc.collect()
    t_ref = time.perf_counter()
    numbers = (compare(fam, config, params_host,
                       [(s.image, s.req.logits) for s in ok]) if ok else {})
    log(f"reference over {len(ok)} requests in "
        f"{time.perf_counter() - t_ref:.3f} s")
    limits = config["limits"]
    checks = {k: {"value": numbers.get(k), "limit": limits[k]}
              for k in limits}
    # the path the cell names, and no other: no batch off the recovery
    # ladder, no kernel call off the native path
    checks["degraded_batches"] = {"value": degraded, "limit": 0}
    checks["fallback_calls"] = {"value": fallback, "limit": 0}
    failed = len(win.served) - len(ok)
    correct = bool(ok) and failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    result = {"correct": correct, "attempted": len(win.served),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_bench()
    cell = cell_parts(bench, args.workload)[0]
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chipbench: the system under test is missing ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache {use_compile_cache()}")
    import jax

    # every program, however quick to compile, is kept for the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    peaks = peaks_for(devs[0].device_kind)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), peaks, bench)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
