"""Pipeline parallelism: GPipe schedule == sequential oracle.

shard_map needs >1 device, and the device count locks at first jax init,
so this test runs in a subprocess with 8 forced host devices.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

PROGRAM = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, %r)
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.parallel.compat import make_mesh
    from repro.parallel.pipeline import pipeline_apply, stage_partition

    mesh = make_mesh((4, 2), ("pod", "model"))
    L, d, n_micro, B = 8, 16, 6, 4
    key = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(key, (L, d, d)) * 0.3,
        "b": jax.random.normal(jax.random.fold_in(key, 1), (L, d)) * 0.1,
    }
    x = jax.random.normal(jax.random.fold_in(key, 2), (n_micro, B, d))

    def layer_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    # sequential oracle
    def oracle(x1):
        y = x1
        for i in range(L):
            y = layer_fn({"w": params["w"][i], "b": params["b"][i]}, y)
        return y
    ref = jnp.stack([oracle(x[i]) for i in range(n_micro)])

    with mesh:
        out = pipeline_apply(params, x, layer_fn, mesh, axis="pod")
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, f"pipeline != sequential oracle: {err}"

    # stage partitioning sanity
    assert stage_partition(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
    assert stage_partition(7, 2) == [(0, 4), (4, 7)]
    print("PIPELINE_OK", err)
""") % str(SRC)


def test_gpipe_matches_sequential():
    res = subprocess.run(
        [sys.executable, "-c", PROGRAM],
        capture_output=True, text=True, timeout=300,
    )
    assert "PIPELINE_OK" in res.stdout, res.stdout + res.stderr
