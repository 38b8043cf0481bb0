"""The splash-attention kernel's block map follows a row's segment ids.

The map alone against a brute-force count from the dense mask (forward, dq
and dkv, grids the library shrank and grids it did not); the kernel with the
rows' maps, interpreted on the CPU at ``SPLASH_BLOCK`` 128, against the
blocked ``jax.numpy`` lowering, outputs and the three gradients; and the
counters a tiny routed run books against the map's own count for its rows.
"""
import numpy
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.ops import transformer

BLOCK = 128


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Rows of four and five of the kernel's blocks (at the chip's block
    size a toy row is one)."""
    monkeypatch.setattr(transformer, "SPLASH_BLOCK", BLOCK)


def _segments(seq, cuts):
    """A row's segment ids: a new document starts at every cut."""
    seg = numpy.ones(seq, numpy.int32)
    for cut in cuts:
        seg[cut:] += 1
    return seg


def _dense(seg, window):
    i = numpy.arange(len(seg))[:, None]
    j = numpy.arange(len(seg))[None, :]
    near = (j <= i) if window is None else (j <= i) & (i - j < window)
    return near & (seg[:, None] == seg[None, :])


#: name -> where a row of 640 tokens is cut
ROWS = {"inside_blocks": [100, 290, 400], "on_a_block_edge": [256],
        "one_document": [], "next_to_an_edge": [128, 384, 385]}


@pytest.mark.parametrize("cuts", ROWS.values(), ids=ROWS.keys())
@pytest.mark.parametrize("which", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("window", [None, 200, 300],
                         ids=["causal", "window200", "window300"])
def test_the_map_visits_the_blocks_that_hold_a_pair(window, which, cuts):
    """Every step the map runs is a block of the static map in which a
    query shares a document with a key it may attend; every step it turns
    off holds none; every such block is run once.  Under a window the
    library shrinks the grid (a step's block is what its ``data_next``
    names), under the causal mask it does not."""
    seq = 640
    seg = _segments(seq, cuts)
    info = transformer._static_maps(seq, window, 1)[
        ("fwd", "dq", "dkv").index(which)]
    dkv = which == "dkv"
    static_mask, static_next = info.block_mask, info.data_next
    # the maps that are followed are the kernel's own
    own = getattr(transformer._splash_kernel(seq, window, 1),
                  which + "_mask_info")
    for mine, its in ((static_mask, own.block_mask),
                      (static_next, own.data_next)):
        assert mine.dtype == its.dtype
        numpy.testing.assert_array_equal(mine, numpy.asarray(its))
    shrunk = static_mask.shape[1:] != (seq // BLOCK, seq // BLOCK)
    assert shrunk == (window is not None)
    block_mask, data_next = (numpy.asarray(a) for a in jax.jit(
        lambda s: transformer.follow_segments(info, s, BLOCK, dkv))(
            jnp.asarray(seg)))
    assert block_mask.dtype == static_mask.dtype
    assert data_next.dtype == static_next.dtype
    assert block_mask.shape == static_mask.shape == data_next.shape

    def pairs(mask, qb, kb):
        return mask[qb * BLOCK:(qb + 1) * BLOCK,
                    kb * BLOCK:(kb + 1) * BLOCK].any()

    full, mask_alone = _dense(seg, window), _dense(numpy.ones_like(seg),
                                                   window)
    run = []
    for i, j in numpy.ndindex(block_mask.shape[1:]):
        if not static_mask[0, i, j]:
            assert not block_mask[0, i, j]      # the library's own padding
            continue
        own = int(static_next[0, i, j])
        qb, kb = (own, j) if dkv else (i, own)
        assert pairs(mask_alone, qb, kb)
        # a block the static mask cuts keeps the library's mark (1: partial)
        assert block_mask[0, i, j] == (
            static_mask[0, i, j] if pairs(full, qb, kb) else 0)
        if block_mask[0, i, j]:
            run.append((qb, kb))
            assert data_next[0, i, j] == own
    n = seq // BLOCK
    assert sorted(run) == [(qb, kb) for qb in range(n) for kb in range(n)
                           if pairs(full, qb, kb)]
    # every query block and every key block keeps its diagonal
    assert {qb for qb, _ in run} == {kb for _, kb in run} == set(range(n))
    # a step that is not run names the block of the next one that is, in
    # the order the grid is walked, and the first again after the last
    order = [(i, j) for j in range(block_mask.shape[2])
             for i in range(block_mask.shape[1])] if dkv else \
        list(numpy.ndindex(block_mask.shape[1:]))
    ahead = None
    for i, j in reversed(order + order):
        if block_mask[0, i, j]:
            ahead = int(static_next[0, i, j])
        elif ahead is not None and cuts:
            assert data_next[0, i, j] == ahead
    if not cuts:
        # nothing is turned off: the library's map, value for value
        numpy.testing.assert_array_equal(block_mask, static_mask)
        numpy.testing.assert_array_equal(data_next, static_next)
    visited, static = numpy.asarray(transformer.block_maps(
        jnp.asarray(seg[None]), window)[1])
    if which == "fwd":
        assert (visited, static) == (len(run), int((static_mask > 0).sum()))


def test_ids_in_any_order_turn_off_only_disjoint_ranges():
    """Ids that are not sorted: a block is turned off only where the two
    ranges of ids are disjoint, so no pair is lost."""
    seg = numpy.repeat([5, 2, 9, 2], BLOCK).astype(numpy.int32)
    info = transformer._static_maps(len(seg), None, 1)[0]
    block_mask, _ = transformer.follow_segments(info, jnp.asarray(seg),
                                                BLOCK)
    want = [[1, 0, 0, 0], [0, 1, 0, 0], [0, 0, 1, 0], [0, 1, 0, 1]]
    numpy.testing.assert_array_equal(numpy.asarray(block_mask)[0] > 0,
                                     numpy.array(want, bool))


#: name -> (the rows' cuts, window, query heads, key-value heads)
CALLS = {
    "boundaries_inside_blocks": ([[150, 300]], None, 2, 1),
    "a_boundary_on_a_block_edge": ([[256]], None, 2, 1),
    "a_one_document_row": ([[]], None, 2, 1),
    "a_window_shorter_than_a_document": ([[256]], 100, 2, 1),
    "seven_heads_on_one": ([[150, 384]], None, 7, 1),
    "one_head_on_one": ([[150, 384]], None, 2, 2),
    "two_rows_of_different_documents": ([[150, 300], [256]], None, 2, 1),
    "two_rows_under_a_window": ([[100, 290, 400], []], 200, 4, 2),
}


@pytest.mark.parametrize("rows, window, heads, kv_heads", CALLS.values(),
                         ids=CALLS.keys())
def test_the_kernel_with_the_rows_maps_equals_the_blocked_lowering(
        rows, window, heads, kv_heads):
    """Outputs and the gradients of queries, keys and values: a block that
    is skipped held only masked pairs, whose weights are exactly zero."""
    rs = numpy.random.RandomState(34)
    b, s, hd = len(rows), 512, 128
    q, k, v = (jnp.asarray(rs.normal(0, 1, (b, s, n, hd)), jnp.float32)
               for n in (heads, kv_heads, kv_heads))
    seg = jnp.asarray(numpy.stack([_segments(s, cuts) for cuts in rows]))
    visited, static = numpy.asarray(transformer.block_maps(seg, window)[1])
    assert (visited < static) == any(rows)

    def blocked(q, k, v):
        return transformer.attend(q, k, v, seg, 64, True, window)

    def splash(q, k, v):
        return transformer.attend_splash(q, k, v, seg, window,
                                         interpret=True)

    def grads(fn):
        return jax.grad(lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2))(
            q, k, v)

    numpy.testing.assert_allclose(splash(q, k, v), blocked(q, k, v),
                                  rtol=2e-5, atol=2e-5)
    for got, want in zip(grads(splash), grads(blocked)):
        numpy.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-5 * float(jnp.abs(want).max()))


def test_maps_made_once_serve_every_call():
    """What ``forward_tokens`` does: one set of maps a window, handed to
    every layer under it."""
    rs = numpy.random.RandomState(3)
    q, k, v = (jnp.asarray(rs.normal(0, 1, (1, 512, n, 128)), jnp.float32)
               for n in (2, 1, 1))
    seg = jnp.asarray(_segments(512, [200])[None])
    maps, _ = transformer.block_maps(seg, None)
    numpy.testing.assert_array_equal(
        transformer.attend_splash(q, k, v, seg, None, maps, interpret=True),
        transformer.attend_splash(q, k, v, seg, None, interpret=True))


@pytest.fixture(scope="module")
def tiny_routed_run():
    """A routed model of two layers (one global, one under a window of 200
    keys; heads of 128, rows of 512) through StandardWorkflow and the fused
    trainer for two epochs, its attention as the kernel, interpreted: the
    test steers the choice the platform makes on the chip."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.backends import JaxDevice
    from znicz_tpu.core.config import root
    from znicz_tpu.samples.research import routed_lm
    patch = pytest.MonkeyPatch()
    real = transformer._splash_kernel
    patch.setattr(transformer, "SPLASH_BLOCK", BLOCK)
    patch.setattr(transformer, "kernel_suits", lambda seq, head_dim: True)
    patch.setattr(transformer, "_splash_kernel",
                  lambda s, window, rep, interpret=False, block=None: real(
                      s, window, rep, True, block))
    patch.setattr(root.routed_lm, "loader_name", "synthetic_token_rows")
    was = root.common.telemetry.get("enabled", False)
    telemetry.enable()
    telemetry.reset()
    try:
        wf = routed_lm.build(
            layers=routed_lm.make_layers(
                vocab=64, dim=128, heads=2, kv_heads=1, head_dim=128,
                experts=4, top_k=2, held_count=4, hidden=64, n_layers=2,
                window=200),
            loader_config={"minibatch_size": 2, "seq_len": 512,
                           "n_train": 4, "n_valid": 2, "doc_median": 150},
            fused={"window": 2}, decision_config={"max_epochs": 2},
            snapshotter_config={"interval": 1000})
        # the library kernel's index arithmetic is int32: x64 (on in the
        # tests) is off around it, as it is on the chip
        with jax.enable_x64(False):
            wf.initialize(device=JaxDevice())
            wf.run()
        counters = {n: telemetry.counter(n).value for n in (
            "attention.blocks_visited", "attention.blocks_static",
            "attention.forwards_kept", "trainer.readbacks", "trainer.rows")}
        segments = numpy.asarray(wf.loader.token_segments)
        n_valid = wf.loader.n_valid
    finally:
        root.common.telemetry.enabled = was
        patch.undo()
    return counters, segments[n_valid:]


def test_counters_are_the_maps_own_count_of_the_rows_trained(
        tiny_routed_run):
    counters, train_rows = tiny_routed_run
    epochs = 2
    assert counters["trainer.rows"] == epochs * len(train_rows)
    want = epochs * sum(
        numpy.asarray(transformer.block_maps(
            jnp.asarray(train_rows), window)[1])
        for window in (None, 200))
    assert [counters["attention.blocks_visited"],
            counters["attention.blocks_static"]] == want.tolist()
    # four blocks a row: ten steps under the causal mask, nine under the
    # window, and the documents empty some of them
    assert counters["attention.blocks_static"] == \
        epochs * len(train_rows) * (10 + 9)
    assert 0 < counters["attention.blocks_visited"] < \
        counters["attention.blocks_static"]


def test_every_application_keeps_its_forward(tiny_routed_run):
    """Both layers' attention stands in a recomputed residual entry: each
    train step keeps two applications' output and log-sum (minibatch 2)."""
    counters, _ = tiny_routed_run
    steps = counters["trainer.rows"] // 2
    assert counters["attention.forwards_kept"] == 2 * steps > 0


def test_counts_ride_the_epochs_one_readback(tiny_routed_run):
    counters, _ = tiny_routed_run
    assert counters["trainer.readbacks"] == 2


def test_where_no_kernel_runs_nothing_is_counted():
    """On this platform attention is the blocked lowering: the accumulator
    carries the count and it stays zero."""
    from znicz_tpu.parallel import fused
    from znicz_tpu.samples.research import routed_lm
    net = fused.FusedNet(routed_lm.make_layers(n_layers=1), (32,),
                         objective="tokens")
    assert net.window_acc_zeros()["attention_blocks"].tolist() == [0, 0]
    ids = jnp.zeros((2, 32), jnp.int32)
    emit = fused.forward_tokens(net.params, ids, jnp.ones_like(ids), ids,
                                net.specs, net.topology)
    assert "attention_blocks" not in emit
