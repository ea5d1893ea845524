"""Pallas remote-DMA exchange (transport_pallas) vs the XLA all_to_all:
identical results, standalone and through a full DSM step, on the virtual
CPU mesh (interpreter mode — the same kernel compiles for multi-chip ICI).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sherman_tpu.config import DSMConfig, PAGE_WORDS
from sherman_tpu.parallel import dsm as D
from sherman_tpu.parallel import transport
from sherman_tpu.parallel.mesh import AXIS, make_mesh


def _mesh_exchange(n, arr, impl):
    mesh = make_mesh(n)
    spec = jax.sharding.PartitionSpec(AXIS)

    def inner(x):
        return transport.exchange(x, AXIS, impl=impl)

    fn = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
    return np.asarray(fn(arr))


@pytest.mark.parametrize("n,c,w", [(4, 8, 16), (8, 4, 1)])
def test_exchange_pallas_matches_xla(eight_devices, n, c, w):
    rng = np.random.default_rng(0)
    shape = (n * n * c, w) if w > 1 else (n * n * c,)
    arr = rng.integers(-1000, 1000, shape).astype(np.int32)
    out_x = _mesh_exchange(n, arr, "xla")
    out_p = _mesh_exchange(n, arr, "pallas")
    np.testing.assert_array_equal(out_x, out_p)


def test_exchange_pallas_bool_roundtrip(eight_devices):
    n, c = 4, 8
    rng = np.random.default_rng(1)
    arr = rng.integers(0, 2, n * n * c).astype(bool)
    out_x = _mesh_exchange(n, arr, "xla")
    out_p = _mesh_exchange(n, arr, "pallas")
    assert out_p.dtype == np.bool_
    np.testing.assert_array_equal(out_x, out_p)


def test_dsm_step_over_pallas_exchange(eight_devices):
    """Cross-node write/read + CAS through the Pallas-RDMA data plane."""
    from sherman_tpu.ops import bits

    cfg = DSMConfig(machine_nr=4, pages_per_node=64, locks_per_node=64,
                    step_capacity=16, chunk_pages=8,
                    exchange_impl="pallas")
    dsm = D.DSM(cfg)
    addr = bits.make_addr(3, 5)
    page = np.arange(PAGE_WORDS, dtype=np.int32)
    dsm.write_page(addr, page)
    np.testing.assert_array_equal(dsm.read_page(addr), page)

    rows = [{"op": D.OP_CAS, "addr": bits.make_addr(2, 7), "woff": 0,
             "arg0": 0, "arg1": 50 + i, "space": D.SPACE_LOCK}
            for i in range(5)]
    rep = dsm._batch(rows)
    assert rep.ok.sum() == 1
    old = dsm.read_word(bits.make_addr(2, 7), 0, space=D.SPACE_LOCK)
    assert old == 50 + int(np.nonzero(rep.ok)[0][0])


def test_collective_id_distinct_per_shape_family():
    from sherman_tpu.parallel.transport_pallas import _collective_id
    ids = {(_collective_id(n, c, w))
           for n in (2, 4, 8) for c in (16, 64, 512) for w in (1, 8, 262)}
    assert len(ids) == 27, "shape families collided in a tiny sample"


def test_exchange_pallas_unavailable_names_the_knob(monkeypatch):
    """Toolchain-missing fallback: a typed error that tells the operator
    which knob to flip, not a bare AssertionError."""
    from sherman_tpu.parallel import transport_pallas as TP

    monkeypatch.setattr(TP, "HAVE_PALLAS", False)
    with pytest.raises(TP.PallasUnavailableError) as ei:
        TP.exchange_pallas(jnp.zeros((8, 4), jnp.int32), AXIS, 4)
    msg = str(ei.value)
    assert "exchange_impl" in msg and "xla" in msg
    # ...and the pytree wrapper propagates it (the path transport.exchange
    # takes when DSMConfig.exchange_impl == "pallas")
    with pytest.raises(TP.PallasUnavailableError):
        TP.exchange({"a": jnp.zeros(8, jnp.int32)}, AXIS, 4)


def test_exchange_pallas_non32bit_lane_names_the_knob(eight_devices):
    """A 16-bit lane cannot ride the packed int32 buffer: the typed
    ExchangeLaneError says so and names exchange_impl="xla"."""
    from sherman_tpu.parallel import transport_pallas as TP

    n = 4
    mesh = make_mesh(n)
    spec = jax.sharding.PartitionSpec(AXIS)
    arr = np.zeros(n * n * 8, np.int16)

    def inner(x):
        return transport.exchange(x, AXIS, impl="pallas")

    fn = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
    with pytest.raises(TP.ExchangeLaneError) as ei:
        fn(arr)
    msg = str(ei.value)
    assert "int16" in msg and "exchange_impl" in msg and "xla" in msg
