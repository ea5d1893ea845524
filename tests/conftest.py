"""Test harness: run everything on 8 virtual CPU devices.

The multi-chip code path (shard_map over the 'node' mesh) is exercised
without TPU hardware, per the reference's missing-fake-transport lesson
(SURVEY.md §4): the DSM is fully testable in-process.
"""

import os

# jax may already be pre-imported by the interpreter environment, so setting
# JAX_PLATFORMS via os.environ can be too late — update the live config
# instead (the backend is only initialized on first use).
os.environ["JAX_PLATFORMS"] = "cpu"  # override e.g. JAX_PLATFORMS=tpu
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite's cost on a small host is almost
# entirely XLA compiles of the same step shapes; cache them across runs so
# the fast tier gives signal in bounded time after the first population.
from sherman_tpu.utils.compile_cache import setup_compile_cache  # noqa: E402

setup_compile_cache(min_compile_secs=0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tier (differential fuzz, multi-process "
        "clusters, split storms, driver smoke runs); deselected by "
        "default in scripts/run_tests.sh — run with --slow there or "
        "-m '' here")


# -- multihost capability probe ----------------------------------------------
# The multi-process drills (tests/test_multihost.py) need a jaxlib with
# CPU multiprocess collectives (cross-process allgather over gloo); a
# build without them would FAIL the drills on the environment rather
# than the code.  Probe ONCE (two tiny
# subprocesses run a cross-process allgather with a deadline) the first
# time a multihost test is about to run, and pytest.skip with the
# captured reason when the build can't do it.  The probe result is
# cached for the session; capable builds (and real chips) run the
# drills unchanged.

def multihost_capable() -> tuple[bool, str]:
    """(capable, reason) — probed once per session, subprocess-isolated
    so the probe can neither poison nor be poisoned by this process's
    jax runtime.  The probe itself lives in
    ``sherman_tpu.multihost.multihost_capable`` (PR 19); this wrapper
    keeps the historical test-harness entry point."""
    from sherman_tpu.multihost import multihost_capable as probe
    return probe()


def pytest_runtest_setup(item):
    if os.path.basename(str(item.fspath)) == "test_multihost.py":
        ok, reason = multihost_capable()
        if not ok:
            pytest.skip(f"multihost drills need CPU multiprocess "
                        f"collectives — {reason}")


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


def run_insert_kernel(eng, keys, vals, *, use_router=None, with_fresh=True,
                      update_only=False):
    """Drive ONE raw insert step (no engine retry) -> status [n].

    Shared by the kernel-semantics tests (test_batched) and the
    concurrency tests (test_concurrent): statuses are observable because
    the engine's retry loop is bypassed.
    """
    import numpy as np

    from sherman_tpu.ops import bits
    if use_router is None:
        use_router = eng.router is not None
    n = keys.shape[0]
    khi, klo = bits.keys_to_pairs(keys)
    vhi, vlo = bits.keys_to_pairs(vals)
    (khi, _), (klo, _) = eng._pad(khi), eng._pad(klo)
    (vhi, _), (vlo, _) = eng._pad(vhi), eng._pad(vlo)
    active, _ = eng._pad(np.ones(n, bool))
    fn = eng._get_insert(eng._iters(), use_router, with_fresh=with_fresh,
                         update_only=update_only)
    dsm = eng.dsm
    args = [eng._shard(khi), eng._shard(klo), eng._shard(vhi),
            eng._shard(vlo), np.int32(eng.tree._root_addr),
            eng._shard(active)]
    if use_router:
        args.append(eng._shard(eng.router.host_start(khi, klo)))
    with eng._step_mutex:
        if with_fresh:
            args.append(eng._shard(np.zeros(
                eng.cfg.machine_nr * eng.split_slots, np.int32)))
            dsm.pool, dsm.counters, dsm.dirty, st, _log = fn(
                dsm.pool, dsm.locks, dsm.counters, dsm.dirty, *args)
        else:
            dsm.pool, dsm.counters, dsm.dirty, st = fn(
                dsm.pool, dsm.locks, dsm.counters, dsm.dirty, *args)
    return eng._unshard(st)[:n]
