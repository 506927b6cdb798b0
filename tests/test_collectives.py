"""Compressed psum == exact psum within quantization tolerance (subprocess:
needs multiple devices)."""

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
    from jax.sharding import PartitionSpec as P
    from repro.parallel.collectives import compressed_psum
    from repro.parallel.compat import make_mesh, shard_map

    mesh = make_mesh((8,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32)) * 0.01

    def f(xb):
        exact = jax.lax.psum(xb, "data")
        comp = compressed_psum(xb, "data")
        rel = jnp.max(jnp.abs(comp - exact)) / jnp.maximum(jnp.max(jnp.abs(exact)), 1e-9)
        return rel

    with mesh:
        rel = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                        check_vma=False)(x)
    rel = float(rel)
    assert rel < 0.02, rel
    print("COMPRESSED_PSUM_OK", rel)
""") % str(SRC)


def test_compressed_psum_accuracy():
    res = subprocess.run(
        [sys.executable, "-c", PROGRAM],
        capture_output=True, text=True, timeout=300,
    )
    assert "COMPRESSED_PSUM_OK" in res.stdout, res.stdout + res.stderr
