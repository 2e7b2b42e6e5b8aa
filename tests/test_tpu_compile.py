"""Compile for a described TPU v5e, with no chip attached.

Every Pallas kernel in ``repro.kernels`` and the sharded row-verification
program of the device path are compiled by the chip's own compiler at
real widths, so a refusal (block tiling, an op the TPU lowering lacks, a
program that does not fit the chip's memory) fails here instead of on
the chip.  Nothing runs.  The topology is described inside a fixture:
where it cannot be described (no TPU compiler installed), every test of
this file skips.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.euclid import euclid_pallas
from repro.kernels.paa import paa_pallas
from repro.kernels.sax_dist import sax_dist_pallas
from repro.kernels.ssax_dist import ssax_dist_pallas
from repro.kernels.windowed_euclid import windowed_euclid_pallas

#: one chip's quarter of the paper's 6,510,417-row efficiency set
MIRROR_ROWS = 1_627_604
T = 960


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # else the compiler logs to /tmp
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        mp.undo()


@pytest.fixture
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("q_n", [1, 8])
def test_euclid_compiles(one_chip, q_n):
    c = _compiled(lambda x, q: euclid_pallas(x, q),
                  [((256, T), F32), ((q_n, T), F32)], one_chip)
    assert "tpu_custom_call" in c.as_text()


KERNELS = {
    # metering-length rows, two days of half-hours as the window
    "windowed_euclid_q1": (lambda x, q: windowed_euclid_pallas(x, q),
                           [((256, 21_840), F32), ((1, 96), F32)]),
    # a query batch with a ragged row count and stride
    "windowed_euclid_q4": (
        lambda x, q: windowed_euclid_pallas(x, q, stride=7),
        [((300, 3_600), F32), ((4, 240), F32)]),
    "paa": (lambda x: paa_pallas(x, 48), [((1024, T), F32)]),
    "sax_dist": (sax_dist_pallas, [((4096, 48), I32), ((48, 64), F32)]),
    "ssax_dist": (ssax_dist_pallas,
                  [((4096, 10), I32), ((4096, 48), I32), ((10, 16), F32),
                   ((10, 16), F32), ((48, 32), F32), ((48, 32), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_repaired_kernel_compiles(one_chip, name):
    fn, shapes = KERNELS[name]
    assert "tpu_custom_call" in _compiled(fn, shapes, one_chip).as_text()


@pytest.mark.parametrize("chips", [1, 4])
def test_rows_verify_program_fits(topo, monkeypatch, chips):
    """The device verification round at one chip's share of the paper's
    efficiency set: the kernel is in the program, and its temp is about
    one gathered candidate batch, not a relayout of the mirror."""
    from repro.core.distributed import _LANES, _rr_rows_verify_fn
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()          # no trace made for the CPU may be reused
    try:
        mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
        width = T + (-T) % _LANES
        rows = NamedSharding(mesh, P("data", None))
        rep = NamedSharding(mesh, P())
        q_n, batch = 8, 256
        c = _rr_rows_verify_fn(mesh, chips).lower(
            jax.ShapeDtypeStruct((chips * MIRROR_ROWS, width), F32,
                                 sharding=rows),
            jax.ShapeDtypeStruct((q_n, T), F32, sharding=rep),
            jax.ShapeDtypeStruct((q_n, batch), I32, sharding=rep),
            jax.ShapeDtypeStruct((), I32, sharding=rep)).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("chips", [1, 4])
def test_rows_verify_loop_fits(topo, monkeypatch, chips):
    """Every verification round of a dispatch in one program, over a
    stream as wide as the mirror: the kernel is in the program, and its
    temp is still about one gathered candidate batch (the mirror is read
    in place, not relaid or copied into the loop)."""
    from repro.core.distributed import _LANES, _rr_verify_loop_fn
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    try:
        mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
        width = T + (-T) % _LANES
        rows = NamedSharding(mesh, P("data", None))
        rep = NamedSharding(mesh, P())
        q_n, batch, k = 8, 256, 32
        c_w = chips * MIRROR_ROWS

        def arg(shape, dtype, sharding=rep):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        c = _rr_verify_loop_fn(mesh, chips, k, batch,
                               -(-c_w // batch) + 1).lower(
            arg((c_w, width), F32, rows), arg((q_n, T), F32),
            arg((q_n, c_w), F32), arg((q_n, c_w), I32), arg((q_n,), I32),
            arg((q_n,), I32), arg((q_n, k), F32), arg((q_n, k), I32),
            arg((), I32)).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20
