"""Elastic restart: a checkpoint saved on one device count restores onto a
different mesh (subprocess: the parent pytest locked jax to 1 device)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

_SAVE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.checkpoint import save_checkpoint
    params = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((8,))}
    save_checkpoint("%s", 7, {"params": params}, extras={"data": {"next_index": 3}})
    print("SAVED")
""")

_RESTORE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import restore_checkpoint
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    like = {"w": jnp.zeros((8, 8)), "b": jnp.zeros((8,))}
    sh = {"params": {"w": NamedSharding(mesh, P("data", "model")),
                     "b": NamedSharding(mesh, P("model"))}}
    step, out, extras = restore_checkpoint("%s", {"params": like},
                                           shardings=sh)
    assert step == 7 and extras["data"]["next_index"] == 3
    w = out["params"]["w"]
    assert len(w.sharding.device_set) == 4, w.sharding
    np.testing.assert_array_equal(np.asarray(w),
                                  np.arange(64.0).reshape(8, 8))
    print("RESTORED_ELASTIC")
""")


def test_checkpoint_restores_onto_larger_mesh(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    cwd = os.path.dirname(os.path.dirname(__file__))
    ck = str(tmp_path / "ck")
    r1 = subprocess.run([sys.executable, "-c", _SAVE % ck], env=env,
                        capture_output=True, text=True, timeout=300, cwd=cwd)
    assert "SAVED" in r1.stdout, r1.stdout + r1.stderr
    r2 = subprocess.run([sys.executable, "-c", _RESTORE % ck], env=env,
                        capture_output=True, text=True, timeout=300, cwd=cwd)
    assert "RESTORED_ELASTIC" in r2.stdout, r2.stdout + r2.stderr
