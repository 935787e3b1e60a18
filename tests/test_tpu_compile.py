"""The scheduler's device programs compile for one TPU v5e chip.

Nothing runs: the TPU compiler, installed beside the CPU backend, compiles
for a described ``v5e:2x2`` topology, which catches what interpret mode
cannot (Mosaic's tiling rules for a kernel's blocks, VMEM limits).  The
topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The tests skip where no topology can be described.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.maxplus_scan.ops import _maxplus_entries
from repro.kernels.queue_booking.ops import book_stream
from repro.sim.vector_queue import (QueueFlightSim, _raptor_stream_fns,
                                    keygen_queue, wordcount_queue)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def on(sharding, tree):
    """Shapes of ``tree`` (arrays or shape structs) placed on ``sharding``."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                       sharding=sharding), tree)


def f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("W,N", [(15, 1000), (1024, 4096)])
def test_queue_booking_kernel_compiles(one_chip, W, N):
    """block=64, the engines' default, is rounded up to a lane-aligned
    tile; N=1000 also pads the stream up to it."""
    fn = jax.jit(functools.partial(book_stream, block=64, interpret=False))
    compiled = fn.lower(f32(one_chip, 256, N), f32(one_chip, 256, N),
                        f32(one_chip, 256, W)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("W", [15, 1024])
def test_maxplus_kernel_compiles(one_chip, W):
    compiled = _maxplus_entries.lower(
        f32(one_chip, 256, 64, W), f32(one_chip, 256, 64, W),
        f32(one_chip, 256, W), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("make_wl,W,microbatch", [
    (keygen_queue, 15, 64),        # the paper's HA deployment
    (keygen_queue, 1024, 256),     # a fleet at the independence scale
    (wordcount_queue, 15, 64),     # the DAG dependency path
])
def test_streaming_step_compiles(one_chip, make_wl, W, microbatch):
    """The service's draw and step at the accelerator auto config."""
    wl = make_wl()
    sim = QueueFlightSim(wl, num_workers=W, num_azs=3)
    draw_env, draw, step = _raptor_stream_fns(
        sim.W, sim.A, sim.flight, wl.graph, wl.dist, wl.fail_prob, sim._fp,
        sim._policy, 64, "fixpoint", "seq", "xla", False)
    args = (jax.random.PRNGKey(0), jnp.zeros(microbatch, jnp.float32),
            sim.rho, jnp.asarray(wl.task_means, jnp.float32), wl.offset_ms,
            wl.cv, wl.raptor_stage_ms, sim.oh_mu, sim.oh_sigma)
    draw.lower(*on(one_chip, args)).compile()
    events = jax.eval_shape(draw, *args)
    step.lower(on(one_chip, jnp.zeros(W)), on(one_chip, events),
               on(one_chip, draw_env(jax.random.PRNGKey(1))),
               on(one_chip, jnp.float32(sim.slat))).compile()
