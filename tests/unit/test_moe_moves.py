"""The expert layer's two row movements against the plain formulas they
replaced.

``transformer.spread`` (tokens to sorted pairs) and ``transformer.collect``
(sorted pairs to tokens, weighted, float32) run over the pairs held and no
others, by loops whose trip counts are data; each is the other's transpose
by a hand-written rule.  The references here are the formulas of before:
``jnp.take`` over all ``tokens x top_k`` pairs, a mask, a sum, and jax's
own transposes of those.  ``MOVE_ROWS`` is cut to 16 so that the toy
buffers take several turns and the last one is ragged.

Tolerances: a movement copies rows (exact) or sums at most ``top_k``
float32 addends in the slots' order, as the reference does, so float32
values agree to a rounding of the sum's order (1e-6); bfloat16 rows are
rounded once, alike on both sides.
"""
import numpy
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.ops import transformer

EXPERTS, WIDTH = 8, 16

#: case -> (tokens, top_k, (first held, held count), the logits' tilt)
CASES = {
    # every expert held: all the pairs, a multiple of the chunk
    "all": (64, 2, (0, 8), None),
    # a quarter of the experts: about a quarter of the pairs
    "quarter": (64, 2, (2, 2), None),
    # neither the pairs (150), the tokens (50) nor the pairs held a
    # multiple of the chunk
    "ragged": (50, 3, (5, 4), None),
    # no expert of any token is held
    "zero": (40, 2, (0, 2), {2: 9.0, 3: 8.0}),
    # every token's first choice is the one expert held
    "one_expert": (48, 3, (3, 1), {3: 9.0}),
}


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    monkeypatch.setattr(transformer, "MOVE_ROWS", 16)


def _case(name, dtype, seed=3):
    n, k, (first, count), tilt = CASES[name]
    rs = numpy.random.RandomState(seed)
    logits = rs.normal(0, 1, (n, EXPERTS))
    for expert, by in (tilt or {}).items():
        logits[:, expert] += by
    chosen, weights = transformer.route(jnp.asarray(logits, jnp.float32), k)
    flat = chosen.reshape(-1)
    order = jnp.argsort((flat - first) % EXPERTS, stable=True)
    back = jnp.argsort(order).reshape(n, k)
    held = ((flat >= first) & (flat < first + count)).sum(dtype=jnp.int32)
    x = jnp.asarray(rs.normal(0, 1, (n, WIDTH)), dtype)
    rows = jnp.asarray(rs.normal(0, 1, (n * k, WIDTH)), dtype)
    return x, rows, weights, order, back, held


def spread_plain(x, order, back, held):
    """``x[order // k]`` over all the pairs, those not held masked out."""
    k = back.shape[1]
    live = jnp.arange(order.shape[0]) < held
    return jnp.where(live[:, None], jnp.take(x, order // k, axis=0), 0)


def collect_plain(rows, weights, order, back, held):
    """Every token's ``top_k`` rows fetched, float32, weighted with zero
    where the pair is not held, summed."""
    n, k = back.shape
    parts = jnp.take(rows, back.reshape(-1), axis=0).reshape(
        n, k, -1).astype(jnp.float32)
    return (parts * jnp.where(back < held, weights, 0.0)[:, :, None]).sum(
        axis=1)


def _tol(dtype):
    return {"rtol": 2e-6, "atol": 2e-6} if dtype == "float32" \
        else {"rtol": 1e-2, "atol": 1e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", CASES)
def test_spread_is_the_gather_over_all_pairs(name, dtype):
    x, _, _, order, back, held = _case(name, dtype)
    xs, fetched = jax.jit(transformer.spread)(x, order, back, held)
    assert xs.dtype == x.dtype
    numpy.testing.assert_array_equal(
        numpy.asarray(xs, numpy.float32),
        numpy.asarray(spread_plain(x, order, back, held), numpy.float32))
    # whole turns of the chunk over the pairs held, and no more
    assert int(held) <= int(fetched) < int(held) + 16
    assert int(fetched) % 16 == 0
    n, k = back.shape
    expected = {"all": n * k, "zero": 0, "one_expert": n}.get(name)
    assert expected is None or int(held) == expected


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", CASES)
def test_collect_is_the_weighted_sum_over_all_pairs(name, dtype):
    _, rows, weights, order, back, held = _case(name, dtype)
    out, fetched = jax.jit(transformer.collect)(rows, weights, order, back,
                                                held)
    assert out.dtype == jnp.float32
    numpy.testing.assert_allclose(
        out, collect_plain(rows, weights, order, back, held),
        **_tol("float32"))
    n, k = back.shape
    # every held pair's row once, a turn's tokens up to its first one's
    # count, and every token's sum put back in token order
    assert int(held) + n <= int(fetched) <= int(held) + 16 * k + n
    if name == "all":
        assert int(fetched) == n * k + n
    if name == "zero":
        assert int(fetched) == n and not numpy.asarray(out).any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", CASES)
def test_the_transpose_of_spread_is_the_plain_ones(name, dtype):
    x, rows, _, order, back, held = _case(name, dtype)
    aim = rows.astype(jnp.float32)

    def loss(move):
        return lambda x: (move(x, order, back, held).astype(jnp.float32)
                          * aim).sum()
    got = jax.jit(jax.grad(loss(
        lambda *a: transformer.spread(*a)[0])))(x)
    want = jax.grad(loss(spread_plain))(x)
    assert got.dtype == x.dtype
    numpy.testing.assert_allclose(numpy.asarray(got, numpy.float32),
                                  numpy.asarray(want, numpy.float32),
                                  **_tol(dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", CASES)
def test_the_transpose_of_collect_is_the_plain_ones(name, dtype):
    x, rows, weights, order, back, held = _case(name, dtype)
    aim = x.astype(jnp.float32)

    def loss(move):
        return lambda rows, weights: (
            move(rows, weights, order, back, held) * aim).sum()
    got = jax.jit(jax.grad(loss(
        lambda *a: transformer.collect(*a)[0]), argnums=(0, 1)))(
            rows, weights)
    want = jax.grad(loss(collect_plain), argnums=(0, 1))(rows, weights)
    assert got[0].dtype == rows.dtype and got[1].dtype == weights.dtype
    for a, b in zip(got, want):
        numpy.testing.assert_allclose(numpy.asarray(a, numpy.float32),
                                      numpy.asarray(b, numpy.float32),
                                      **_tol(dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_both_movements_recomputed_inside_a_scan(dtype):
    """As ``_run_nodes`` runs the entry: under ``jax.checkpoint`` inside a
    ``lax.scan`` whose passes share the routing."""
    x, _, weights, order, back, held = _case("ragged", dtype)

    def chain(spread, collect):
        @jax.checkpoint
        def entry(y, weights):
            xs = spread(y, order, back, held)
            return collect(xs * 2, weights, order, back, held).astype(
                y.dtype)

        def loss(x, weights):
            y, _ = jax.lax.scan(
                lambda y, _: (y + entry(y, weights), None), x, None,
                length=2)
            return (y.astype(jnp.float32) ** 2).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, weights)

    got = chain(lambda *a: transformer.spread(*a)[0],
                lambda *a: transformer.collect(*a)[0])
    want = chain(spread_plain, collect_plain)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        numpy.testing.assert_allclose(
            numpy.asarray(a, numpy.float32), numpy.asarray(b, numpy.float32),
            rtol=2e-5 if dtype == "float32" else 3e-2,
            atol=2e-5 if dtype == "float32" else 3e-2)
