"""``transport.bucketize`` against a plain NumPy reference: a request's
slot is its stable rank among the active requests bound for the same
node, ``-1`` when inactive or ranked past the bucket's capacity, on
both sides of ``_ONE_HOT_NODES``.  The lowered program ranks with a
linear scan: no ``while`` (a binary search lowers to one) at the
four-node read cell's round-1 shape, and no sort on four nodes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sherman_tpu.parallel import transport


def _reference(dest, active, n_nodes, capacity):
    idx = np.full(dest.shape[0], -1, np.int32)
    for n in range(n_nodes):
        rows = np.flatnonzero(active & (dest == n))[:capacity]
        idx[rows] = n * capacity + np.arange(rows.size)
    return idx


def _even(n_nodes, per_node, inactive, seed):
    """``per_node`` active rows to every node, ``inactive`` more rows
    with any destination, shuffled."""
    rng = np.random.default_rng(seed)
    dest = np.concatenate([np.repeat(np.arange(n_nodes), per_node),
                           rng.integers(0, n_nodes, inactive)])
    active = np.arange(dest.size) < n_nodes * per_node
    p = rng.permutation(dest.size)
    return dest[p], active[p]


def _random(n_nodes, rows, share, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n_nodes, rows), rng.random(rows) < share


# (n_nodes, capacity, inputs)
CASES = {}
for _n, _k in ((2, 700), (4, 300), (8, 100), (9, 100), (256, 6)):
    for _name, _cap in (("below", _k - 1), ("at", _k), ("above", _k + 1)):
        CASES[f"n{_n}-cap_{_name}"] = (
            _n, _cap, lambda n=_n, k=_k: _even(n, k, 3 * k, seed=n))
    CASES[f"n{_n}-uneven"] = (
        _n, _k, lambda n=_n, k=_k: _random(n, 2 * n * k, 0.7, seed=n + 1))
    CASES[f"n{_n}-one_dest"] = (
        _n, 500, lambda n=_n: (np.full(900, n - 1), np.ones(900, bool)))
    CASES[f"n{_n}-none_active"] = (
        _n, 8, lambda n=_n: _random(n, 1000, 0.0, seed=n + 2))
    CASES[f"n{_n}-r1"] = (
        _n, 1, lambda n=_n: (np.array([n - 1]), np.ones(1, bool)))
    CASES[f"n{_n}-r1_inactive"] = (
        _n, 1, lambda n=_n: (np.array([0]), np.zeros(1, bool)))
# the four-node read cell's round 1 (R = 1,572,864 unique rows a chip)
CASES["cell_round1"] = (
    4, 417_792, lambda: _random(4, 1_572_864, 0.97, seed=5))
CASES["cell_round1_overflow"] = (
    4, 300_000, lambda: _random(4, 1_572_864, 1.0, seed=6))


@pytest.mark.parametrize("case", sorted(CASES))
def test_bucketize_matches_reference(case):
    n_nodes, capacity, make = CASES[case]
    dest, active = make()
    dest = np.asarray(dest, np.int32)
    active = np.asarray(active, bool)
    bucket_idx, routed = jax.jit(transport.bucketize, static_argnums=(2, 3))(
        jnp.asarray(dest), jnp.asarray(active), n_nodes, capacity)
    want = _reference(dest, active, n_nodes, capacity)
    np.testing.assert_array_equal(np.asarray(bucket_idx), want)
    np.testing.assert_array_equal(np.asarray(routed), want >= 0)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("n_nodes", [4, 256])
def test_bucketize_lowers_without_a_loop(platform, n_nodes):
    """At round 1's shape: no ``while``; a sort only past
    ``_ONE_HOT_NODES`` nodes."""
    R, C = 1_572_864, 417_792
    f = jax.jit(transport.bucketize, static_argnums=(2, 3))
    text = f.trace(jax.ShapeDtypeStruct((R,), jnp.int32),
                   jax.ShapeDtypeStruct((R,), jnp.bool_), n_nodes, C).lower(
        lowering_platforms=(platform,)).as_text()
    assert "stablehlo.while" not in text
    assert ("stablehlo.sort" in text) == (
        n_nodes > transport._ONE_HOT_NODES)
