"""Chip smoke test: Neural Cache serving of Inception v3 on one TPU chip.

Serves Inception v3 at its published widths (``inception.FULL``: 299 px
input, 1001 classes, seeded random weights) through the normal entry
point, ``launch/serve.py::NCServingEngine``, with every bit-serial GEMM
running as a compiled Pallas kernel (the ``pallas`` backend, the default
on a TPU).  Two seeded images are drained as two batches of one, the
second warm.  Checks:

* both requests completed, none failed, no batch degraded (no recovery
  rung ran, the float one above all);
* the ``pallas`` backend dispatched natively and never fell back;
* the first image's stem convolutions (``Conv2d_1a_3x3``,
  ``Conv2d_2a_3x3``, ``Conv2d_2b_3x3``) give int32 accumulators
  byte-identical to the ``host`` reference backend.

Run from the repository root on a machine with one TPU chip::

    python chip_smoke.py

It exits non-zero and prints no result where JAX finds no TPU, or where
the repository's sources are missing.  The timings it prints are host
wall-clock around device work (compilation included on the cold batch);
they are bring-up diagnostics, not benchmark numbers.  The last line of
standard output is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"
N_IMAGES = 2
SEED = 0
STEM_CHECK = ("Conv2d_1a_3x3", "Conv2d_2a_3x3", "Conv2d_2b_3x3")


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def serve(engine, images) -> tuple[list, list[float]]:
    """Submit ``images`` and drain them with ``run()``; returns the
    completed requests and the wall time of each batch's forward."""
    from repro.launch.serve import NCRequest

    walls: list[float] = []
    forward = engine._forward

    def timed(x, schedule):
        t0 = time.perf_counter()
        out = forward(x, schedule)
        walls.append(time.perf_counter() - t0)
        return out

    engine._forward = timed
    for rid, img in enumerate(images):
        engine.submit(NCRequest(rid=rid, image=img))
    done = engine.run()
    engine._forward = forward
    return done, walls


def stem_check(engine, image, names=STEM_CHECK) -> None:
    """Run the first stem convolutions of ``image`` through the ``pallas``
    and ``host`` backends on identical inputs; their int32 accumulators
    must agree byte for byte.  Each next input is the layer's requantized
    output, as in the network."""
    import numpy as np

    from repro.core import nc_layers as nc
    from repro.core import nc_network
    from repro.core import quantize as q
    from repro.core import simulator as sim

    specs = {s.name: s for s in engine.specs}
    plans = {p.spec.name: p for p in engine._schedule_for(1).layers}
    actq = np.clip(np.round(image[None] * np.float32(255.0)), 0,
                   255).astype(np.uint8)
    qps = [q.QuantParams(scale=np.float32(1.0 / 255.0), zero_point=0)]
    stem = dict(engine.config.stem)
    for name in names:
        _, _, _, _, stride, pad = stem[name]
        wq, w_qp, _ = engine.wpack[name]
        accs = {}
        for backend in ("pallas", "host"):
            t0 = time.perf_counter()
            acc, _ = nc.nc_conv2d(actq, wq, qps, w_qp, stride, padding=pad,
                                  geom=engine.geom, layer_spec=specs[name],
                                  plan=plans[name], engine=backend)
            accs[backend] = np.asarray(acc)
            _log(f"stem {name} {backend}: {accs[backend].shape} "
                 f"{accs[backend].dtype} in "
                 f"{time.perf_counter() - t0:.3f} s")
        if not (accs["pallas"].dtype == accs["host"].dtype
                and np.array_equal(accs["pallas"], accs["host"])):
            raise AssertionError(f"{name}: pallas accumulators differ from "
                                 f"the host reference")
        actq, qps = nc_network._nc_run_conv(
            name, actq, qps, stem[name], nc_network._Exec(
                engine.wpack, specs, plans, engine.geom, sim.SimConstants(),
                "pallas", []))
    _log(f"stem check: {', '.join(names)} byte-identical to host")


def main() -> int:
    if not (SRC / "repro").is_dir():
        print("chip_smoke: run from a checkout of the repository "
              f"({SRC} is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    _log(f"device {dev.device_kind} x{len(jax.devices())}, "
         f"compile cache {cache_dir}")

    import numpy as np

    from repro.core import backends
    from repro.launch.serve import NCServingEngine
    from repro.models import inception

    compiles = {"n": 0, "s": 0.0}

    def on_compile(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    cfg = inception.FULL
    t0 = time.perf_counter()
    params = inception.init_params(jax.random.key(SEED), config=cfg)
    engine = NCServingEngine(params, cfg, max_batch=1)
    _log(f"{cfg.name}: engine built in {time.perf_counter() - t0:.3f} s")
    images = np.random.default_rng(SEED).random(
        (N_IMAGES, cfg.img, cfg.img, 3), dtype=np.float32)

    backends.dispatch_stats_clear()
    done, walls = serve(engine, images)
    st = engine.stats()
    disp = backends.dispatch_stats()
    _log(f"batch walls (s, host clock, first includes compiles): {walls}")
    _log(f"compiles {compiles['n']} in {compiles['s']:.3f} s, of which "
         f"{backends._pallas_exact._cache_size()} pallas adapter programs; "
         f"dispatch {disp}")
    assert len(done) == N_IMAGES, (len(done), st["errors"])
    assert st["failed"] == 0 and st["degraded_batches"] == 0, st
    assert all(r.degraded is None for r in done)
    for r in done:
        assert r.logits.shape == (cfg.classes,), r.logits.shape
        assert np.isfinite(r.logits).all()
    assert disp["pallas"]["native"] > 0, disp
    assert disp["pallas"]["fallback"] == 0, disp
    assert disp["host"]["native"] == disp["jit"]["native"] == 0, disp

    stem_check(engine, images[0])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
