"""The Pallas kernels of the main path compiled for a TPU v5e that is
described, not attached (rehearsal 3 of the on-chip-measurement guide):
Mosaic must accept them at real widths — the 100 M-key pool of 2^22
pages and 1 M rows, and the 4-chip exchange at the bucket size
``chip_smoke.py --chips 4`` runs.  Nothing executes here; a compile that
passes is not a chip run.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every xdist worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from sherman_tpu import config as C
from sherman_tpu.ops import pallas_page as PP
from sherman_tpu.parallel import transport_pallas as TP

POOL_PAGES = 1 << 22   # bench.py's pool at 100 M keys (4.3 GB)
ROWS = 1 << 20
N_CHIPS = 4
BUCKET = 65_536        # chip_smoke --chips 4 step_capacity per peer


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # these executables can be written to the persistent cache but never
    # read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args, **jit_kw) -> str:
    txt = jax.jit(fn, **jit_kw).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    return txt


def test_descent_round_compiles_for_v5e(one_chip):
    pool = jax.ShapeDtypeStruct((POOL_PAGES, C.PAGE_WORDS), jnp.int32,
                                sharding=one_chip)
    v = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((ROWS,), jnp.bool_, sharding=one_chip)
    _compiled_text(lambda *a: PP.descent_round(*a, interpret=False),
                   pool, v, v, v, b)


def test_writeback_compiles_for_v5e(one_chip):
    pool = jax.ShapeDtypeStruct((POOL_PAGES, C.PAGE_WORDS), jnp.int32,
                                sharding=one_chip)
    v = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((ROWS,), jnp.bool_, sharding=one_chip)
    lanes = (C.L_VER_W, C.L_KHI_W, C.L_KLO_W, C.L_VHI_W, C.L_VLO_W)
    ent = jax.ShapeDtypeStruct((ROWS, len(lanes)), jnp.int32,
                               sharding=one_chip)
    _compiled_text(
        lambda *a: PP.writeback(*a, field_w=lanes, interpret=False),
        pool, v, v, b, ent, donate_argnums=0)


def test_gather_pages_compiles_for_v5e(one_chip):
    pool = jax.ShapeDtypeStruct((POOL_PAGES, C.PAGE_WORDS), jnp.int32,
                                sharding=one_chip)
    v = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    _compiled_text(lambda *a: PP.gather_pages(*a, interpret=False),
                   pool, v)


@pytest.mark.parametrize("width", [1, 257])
def test_exchange_pallas_compiles_for_four_v5e(topo, width):
    """The compiled form the interpreter cannot reach (use_barrier=True:
    barrier semaphore, cross-device signal/wait, posted remote copies)
    over a 4-chip mesh: a 1-word request lane and the packed page reply
    (256 words + ok)."""
    mesh = Mesh(topo.devices[:N_CHIPS], ("node",))
    spec = P("node")
    fn = jax.shard_map(
        lambda x: TP.exchange_pallas(x, "node", N_CHIPS, interpret=False),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    arg = jax.ShapeDtypeStruct((N_CHIPS * N_CHIPS * BUCKET, width),
                               jnp.int32,
                               sharding=NamedSharding(mesh, spec))
    _compiled_text(fn, arg)
