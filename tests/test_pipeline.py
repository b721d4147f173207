"""GPipe schedule: bit-exact vs the unpipelined layer stack.

Needs >1 device, so the check runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (the parent pytest
process has already locked jax to 1 CPU device).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.distributed.pipeline import gpipe_apply, split_stages, bubble_fraction

    S, L, M, MB, D = 4, 8, 6, 2, 16
    mesh = jax.make_mesh((S,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    k = jax.random.key(0)
    Ws = jax.random.normal(k, (L, D, D), jnp.float32) / jnp.sqrt(D)
    x = jax.random.normal(jax.random.key(1), (M, MB, D), jnp.float32)

    def layer_scan(W_stack, h):
        def body(c, W):
            return jnp.tanh(c @ W), None
        out, _ = jax.lax.scan(body, h, W_stack)
        return out

    # reference: all layers, no pipeline
    ref = jax.vmap(lambda xm: layer_scan(Ws, xm))(x)

    staged = split_stages({"W": Ws}, S)["W"]   # [S, L/S, D, D]
    out = gpipe_apply(lambda p, h: layer_scan(p, h), staged, x, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert abs(bubble_fraction(S, M) - 3/9) < 1e-9
    print("GPIPE_OK")
""")


def test_gpipe_matches_unpipelined():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "GPIPE_OK" in r.stdout, r.stdout + r.stderr
