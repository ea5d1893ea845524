"""chip_smoke.py rehearsed on the CPU mesh at a tiny size: every phase
function the chip run calls, the refusal of a non-TPU backend, and the
one compile-cache rule every entry point follows."""

import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as CS  # noqa: E402

KEYS, BATCH, SEED = 6000, 1024, 3


@pytest.fixture(scope="module")
def ctx():
    return CS.phase_load(KEYS, BATCH, SEED)


def test_reference_model_matches_a_dict():
    rng = np.random.default_rng(0)
    keys = np.unique(rng.integers(1, 1 << 40, 500, dtype=np.uint64))
    ref = CS.Reference(keys, keys + np.uint64(1))
    truth = {int(k): int(k) + 1 for k in keys}
    upd = np.concatenate([keys[:50], np.arange(3, 40, 3, dtype=np.uint64)])
    ref.upsert(upd, upd * np.uint64(2))
    truth.update({int(k): 2 * int(k) for k in upd})
    ref.delete(keys[50:90])
    for k in keys[50:90]:
        truth.pop(int(k))
    assert ref.keys.tolist() == sorted(truth)
    assert ref.vals.tolist() == [truth[k] for k in sorted(truth)]
    q = np.concatenate([keys, np.asarray([2, 1 << 41], np.uint64)])
    v, f = ref.lookup(q)
    assert f.tolist() == [int(k) in truth for k in q]
    assert all(int(x) == truth[int(k)] for k, x, hit in zip(q, v, f) if hit)
    lo, hi = int(ref.keys[10]), int(ref.keys[20])
    assert ref.range(lo, hi)[0].tolist() == sorted(truth)[10:20]


def test_phase_load(ctx):
    assert len(ctx["ref"]) == KEYS
    assert ctx["occupancy"][0] > 0
    assert ctx["stats"]["leaves"] >= KEYS // 36


def test_phase_search(ctx):
    assert "all found" in CS.phase_search(ctx)
    assert ctx["n_uniq"] <= BATCH


def test_phase_staged(ctx):
    assert "verified on device" in CS.phase_staged(ctx, steps=2)


def test_phase_mutate(ctx):
    CS.writer_engine(ctx, 512)
    assert ctx["eng"].B == 512
    n0 = len(ctx["ref"])
    assert "structure valid" in CS.phase_mutate(ctx, ops=256)
    assert len(ctx["ref"]) != n0


def test_phase_serve(ctx):
    out = CS.phase_serve(ctx, widths=(128, 512), ops=64)
    assert "10 requests" in out


def test_phase_kernels(ctx):
    out = CS.phase_kernels(ctx, rows=256)
    assert "bit-identical" in out


def test_phase_engines_pallas_matches_model_and_xla_pool():
    out = CS.phase_engines(2000, 256, SEED, None, ops=128)
    assert "bit-identical to the xla engine's" in out


def test_release_frees_the_pool():
    c = CS.phase_load(2000, 128, SEED)
    pool = c["tree"].dsm.pool
    CS.release(c)
    assert pool.is_deleted() and not c


def test_check_raises_smoke_error(ctx):
    q = ctx["ref"].keys[:4]
    with pytest.raises(CS.SmokeError):
        CS.check_lookup(ctx["ref"], q, np.zeros(4, np.uint64),
                        np.ones(4, bool), "wrong values")


def test_four_node_path_pools_equal_across_exchange_impl():
    ctxs = {}
    keyspace = None
    for impl in ("xla", "pallas"):
        c = CS.phase_load(3000, 256, SEED, nodes=4, exchange_impl=impl,
                          keyspace=keyspace)
        keyspace = c["keyspace"]
        assert len(c["occupancy"]) == 4 and min(c["occupancy"]) > 0
        assert "== model" in CS.phase_routed(c, ops=128)
        ctxs[impl] = c
    assert "bit-identical" in CS.phase_pools_equal(ctxs["xla"],
                                                   ctxs["pallas"])


def test_main_refuses_a_non_tpu_backend(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert CS.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_main_refuses_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from sherman_tpu.utils import compile_cache as CC
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CC.setup_compile_cache(0.5) == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == CC.DEFAULT_DIR


def test_compile_cache_honours_the_env(monkeypatch, tmp_path):
    from sherman_tpu.utils import compile_cache as CC
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.setup_compile_cache(0.5) == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before
    r = subprocess.run(
        [sys.executable, "-c", "import jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
        capture_output=True, text=True, timeout=120)
    assert r.stdout.strip() == str(tmp_path)
