"""Raptor JAX combinators under a real multi-device mesh.

jax fixes the device count at first init, so these run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (per the dry-run rule:
never set that flag globally for the test process).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.jaxops import first_finisher, k_of_n_mean, masked_mean
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("pod", "model"))

    # --- first_finisher: everyone adopts the min-latency member's value ---
    def member(lat, val):
        adopted, winner = first_finisher(val, lat[0], "pod")
        return adopted, jnp.broadcast_to(winner, (1,))

    lats = jnp.array([3.0, 1.0, 2.0, 5.0])
    vals = jnp.arange(4 * 6, dtype=jnp.float32).reshape(4, 6)  # per-pod rows
    f = jax.shard_map(member, mesh=mesh,
                      in_specs=(P("pod"), P("pod", None)),
                      out_specs=(P("pod", None), P("pod")), check_vma=False)
    adopted, winner = jax.jit(f)(lats, vals)
    a = np.asarray(adopted)
    assert np.all(np.asarray(winner) == 1), winner
    for r in range(4):
        np.testing.assert_allclose(a[r], np.asarray(vals)[1], rtol=1e-6)

    # --- masked_mean: degraded flight drops dead members ---
    def member2(h, val):
        m, n = masked_mean(val, h[0], "pod")
        return m, jnp.broadcast_to(n, (1,))

    health = jnp.array([1.0, 0.0, 1.0, 1.0])
    f2 = jax.shard_map(member2, mesh=mesh,
                       in_specs=(P("pod"), P("pod", None)),
                       out_specs=(P("pod", None), P("pod")), check_vma=False)
    m, n = jax.jit(f2)(health, vals)
    expect = np.asarray(vals)[[0, 2, 3]].mean(axis=0)
    np.testing.assert_allclose(np.asarray(m)[0], expect, rtol=1e-6)
    assert np.all(np.asarray(n) == 3.0)

    # --- k_of_n_mean: keep the 2 fastest pods ---
    def member3(lat, val):
        return k_of_n_mean(val, lat[0], 2, "pod")

    f3 = jax.shard_map(member3, mesh=mesh,
                       in_specs=(P("pod"), P("pod", None)),
                       out_specs=P("pod", None), check_vma=False)
    km = jax.jit(f3)(lats, vals)
    expect = np.asarray(vals)[[1, 2]].mean(axis=0)   # lats 1.0 and 2.0
    np.testing.assert_allclose(np.asarray(km)[0], expect, rtol=1e-6)
    print("JAXOPS_OK")
""")


def test_jaxops_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "JAXOPS_OK" in r.stdout, r.stdout + r.stderr
