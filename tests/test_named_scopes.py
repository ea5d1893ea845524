"""The serve and prep programs name their phases with ``jax.named_scope``
(the names ``benchmarks/scopes.py`` attributes device time to): each
scope is in the op_name paths of the lowered program."""

import re

import jax
import numpy as np
import pytest

from sherman_tpu.ops import bits

SALT = 0x5E17_AB1E_5A17
N_KEYS, BATCH = 20_000, 2048


def _engine():
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    cfg = DSMConfig(machine_nr=1, pages_per_node=2048, locks_per_node=512,
                    step_capacity=BATCH, chunk_pages=32)
    tree = Tree(Cluster(cfg))
    eng = batched.BatchedEngine(tree, batch_per_node=BATCH)
    keys = bits.mix64_np(np.arange(N_KEYS, dtype=np.uint64)
                         ^ np.uint64(SALT))
    order = np.argsort(keys)
    batched.bulk_load(tree, keys[order],
                      (keys ^ np.uint64(0xDEADBEEF))[order], fill=0.8)
    eng.attach_router()
    return eng


def _shape(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=getattr(x, "sharding", None))
    return x


def _lowered(progs, run):
    """Debug-info HLO text of each labelled program's first call inside
    ``run()`` (the call's arguments recorded, the program lowered)."""
    seen = {}
    saved = [(p, p._fn) for p in progs]

    class Rec:
        def __init__(self, p, fn):
            self.p, self.fn = p, fn

        def _cache_size(self):
            return self.fn._cache_size()

        def __call__(self, *args):
            seen.setdefault(self.p.label, jax.tree.map(_shape, args))
            return self.fn(*args)

    for p, fn in saved:
        p._fn = Rec(p, fn)
    try:
        run()
    finally:
        for p, fn in saved:
            p._fn = fn
    return {p.label: fn.lower(*seen[p.label]).as_text(debug_info=True)
            for p, fn in saved}


def _staged_read():
    from sherman_tpu.workload.device_prep import make_staged_step
    eng = _engine()
    step, (new_carry, tb, rt, rk) = make_staged_step(
        eng, n_keys=N_KEYS, theta=0.99, salt=SALT, batch=BATCH,
        dev_b=BATCH, log2_bins=16, fusion="aligned")

    def run():
        carry = step(eng.dsm.pool, eng.dsm.counters, tb, rt, rk,
                     new_carry())[1]
        jax.block_until_ready(step.drain(carry))

    return _lowered([step.jprep, step.jserve], run)


def _staged_mixed():
    from sherman_tpu.workload.device_prep import make_staged_mixed_step
    eng = _engine()
    step, (new_carry, tb, rt, rk) = make_staged_mixed_step(
        eng, n_keys=N_KEYS, theta=0.99, salt=SALT, batch=BATCH,
        read_ratio=0.5, dev_rb=1024, dev_wb=1024, log2_bins=16,
        fusion="pipelined")
    dsm = eng.dsm

    def run():
        pool, counters, carry = step(dsm.pool, dsm.locks, dsm.counters,
                                     tb, rt, rk, new_carry())
        jax.block_until_ready(step.drain(carry))
        dsm.pool, dsm.counters = pool, counters

    return _lowered([step.jprep, step.jserve], run)


LOOPS = {"read": _staged_read, "mixed": _staged_mixed}
CASES = [
    ("read", "serve", ("descend", "fanout")),
    ("read", "prep", ("sample", "combine", "router_probe")),
    ("mixed", "serve", ("descend", "snapshot", "lock", "apply",
                        "writeback", "fanout")),
    ("mixed", "prep", ("sample", "combine", "router_probe")),
]


@pytest.fixture(scope="module")
def lowered(eight_devices):
    return {}


@pytest.mark.parametrize("loop,program,scopes", CASES)
def test_program_names_its_scopes(loop, program, scopes, lowered):
    if loop not in lowered:
        lowered[loop] = LOOPS[loop]()
    texts = lowered[loop]
    label = [k for k in texts if program in k.split(".")[-1]
             or (program == "serve" and k == "engine.search_fanout")]
    assert len(label) == 1, list(texts)
    txt = texts[label[0]]
    for scope in scopes:
        assert re.search(rf'/{scope}[/"]', txt), (label[0], scope)
