"""Compile the D4M update steps for a TPU v5e chip that is described, not
attached.

The TPU compiler is installed wherever libtpu is, so these tests refuse what
the chip's compiler would refuse (unaligned tiles, unsupported Mosaic ops,
more VMEM than a kernel may claim, a program over HBM) at no chip time.
Nothing runs: they say nothing about results or speed.  The topology is
described inside a module fixture, never at import time: only one process
may load libtpu, and every test worker imports this file.  The widths here
compile in seconds; full-width compiles are for ``chip_smoke.py`` rehearsals.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import assoc, hierarchical, multistream
from repro.kernels.hier_cascade import ops as cascade_ops

# XLA engines: small widths (their compile time grows with the layer widths)
CUTS, TOP, BATCH, K = (64, 512), 4096, 64, 8
# the kernel at chip_smoke's run (a) shapes: K=32 lanes, README cuts, and
# the widest top layer whose merge fits the kernel's VMEM budget
KERNEL = dict(k=32, cuts=(1024, 8192), top=(1 << 21) - 9728, batch=512)
HBM_BYTES = 16 * 10**9  # one v5e chip
# assoc.add past what the compiler keeps in VMEM, so its temporaries are in
# HBM: a 4.2M-slot layer takes a 524K-slot one, non-power-of-two widths
ADD_WIDTHS = ((1 << 22) - 4321, (1 << 19) + 5)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _batch(k, batch, sharding):
    shape = (batch,) if k is None else (k, batch)
    ids = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
    return ids, ids, jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _step(engine):
    """(state shapes, step fn, K or None, batch) of one engine's update."""
    if engine == "single":
        state = jax.eval_shape(lambda: hierarchical.init(CUTS, TOP, BATCH))
        fn = lambda h, r, c, v: hierarchical.update_triples(h, r, c, v, CUTS)
        return state, fn, None, BATCH
    if engine == "packed":
        state = jax.eval_shape(lambda: multistream.init_packed(K, CUTS, TOP, BATCH))
        fn = lambda h, r, c, v: multistream.packed_update(h, r, c, v, CUTS)
        return state, fn, K, BATCH
    # the session's pallas step: state kept in the kernel's tiled layout
    k, cuts, top, batch = KERNEL["k"], KERNEL["cuts"], KERNEL["top"], KERNEL["batch"]
    state = jax.eval_shape(
        lambda: cascade_ops.tile_state(cascade_ops.init_state(k, cuts, top, batch)[0])
    )
    caps = hierarchical.telescoped_caps(cuts, top, batch)
    fn = lambda h, r, c, v: cascade_ops.cascade_update_tiled(
        h, r, c, v, cuts, caps, interpret=False
    )
    return state, fn, k, batch


@pytest.mark.parametrize("engine", ["single", "packed", "pallas"])
def test_update_step_compiles_for_v5e(one_chip, engine):
    state, fn, k, batch = _step(engine)
    compiled = (
        jax.jit(fn, donate_argnums=(0,))
        .lower(_shapes(state, one_chip), *_batch(k, batch, one_chip))
        .compile()
    )
    mem = compiled.memory_analysis()
    live = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    )
    assert 0 < live < HBM_BYTES
    hlo = compiled.as_text()
    # the pallas engine runs the Mosaic kernel, never the interpreter
    assert ("tpu_custom_call" in hlo) == (engine == "pallas")
    if engine == "pallas":
        # layers go into the kernel in place: no relayout of the state
        assert mem.temp_size_in_bytes < mem.argument_size_in_bytes // 100


def test_mesh_step_compiles_for_four_v5e_chips(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    engine = multistream.MultiStreamEngine(
        mesh, CUTS, TOP, BATCH, instances_per_device=2
    )
    sharded = NamedSharding(mesh, P("data"))
    state = jax.eval_shape(
        lambda: multistream.init_packed(engine.n_instances, CUTS, TOP, BATCH)
    )
    compiled = engine.update.lower(
        _shapes(state, sharded), *_batch(engine.n_instances, BATCH, sharded)
    ).compile()
    hlo = compiled.as_text()
    # K x D independent instances: no collective on the update path
    for op in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
        assert op not in hlo


def test_add_compiles_for_v5e_without_gather_or_scatter(one_chip):
    """The merge moves elements by contiguous shifts alone, and its
    temporaries stay under 1.5 times its inputs (the rank merge it replaced
    took 1.7 times, with four gathers and six scatters)."""
    m, n = ADD_WIDTHS
    a = jax.eval_shape(lambda: assoc.empty(m))
    b = jax.eval_shape(lambda: assoc.empty(n))
    compiled = (
        jax.jit(lambda a, b: assoc.add(a, b, cap=m))
        .lower(_shapes(a, one_chip), _shapes(b, one_chip))
        .compile()
    )
    hlo = compiled.as_text()
    for op in ("gather(", "scatter(", "sort("):
        assert op not in hlo, op
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < 1.5 * mem.argument_size_in_bytes
