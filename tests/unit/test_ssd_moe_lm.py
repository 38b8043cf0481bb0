"""A language model of state-space (Mamba-2) mixers, attention with no
position and shared and routed relu^2 experts (one chip's share) through the
fused path, at toy widths.

The chunked scan against the recurrence token by token at chunk sizes that
do and do not divide the documents; a row of two documents against each
document alone; the mixer's output and every leaf's gradient, and the
non-gated experts', against the plain reference ``benchmarks/reference/
ssd_moe_lm.py``; sixteen shares adding up to the uncut expert layer with
the shared expert counted once; the initial draw against the reference's;
the leaves that take no weight decay; the scan's counts on a known layout;
and the fused window of the tiny cell (``benchmarks/tests/tiny_ssd``)
against the reference over four AdamW steps.

Tolerances: float32 on the CPU at ``highest`` matmul precision on both
sides (``tests/conftest.py``), so gaps are the order of float32 sums over
the widths and the row (a few 1e-7 relative; the limits below give them one
to two orders of room); a product in bf16 moves an output by 1e-3 relative
and fails every one of them.
"""
import json
import math
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmarks import rehearse
from benchmarks.reference import ssd_moe_lm as ref
from znicz_tpu.ops import transformer
from znicz_tpu.parallel import fused
from znicz_tpu.samples.research import hybrid_moe_lm

TESTS = os.path.join(os.path.dirname(os.path.abspath(rehearse.__file__)),
                     "tests", "tiny_ssd")
CELL = {"name": "tiny_ssd_lm.train_s32_b2", "config": "tiny_ssd_lm",
        "traffic": "train_s32_b2", "chips": 1}
NUMBERS = ("loss_worst_step", "logit_rel_diff", "m1_worst_leaf",
           "dparam_worst_leaf", "route_flip_share", "flip_margin_p999",
           "choice_bias_tilt", "weight_share_gap", "bias_gap",
           "window_rows_gap", "window_tokens_gap", "window_load_gap",
           "hyper_feed_gap", "epoch_train_rows_gap", "epoch_valid_rows_gap",
           "epoch_train_tokens_gap", "epoch_valid_tokens_gap")
DIM = 24


def _load(*parts):
    with open(os.path.join(TESTS, *parts)) as f:
        return json.load(f)


def _rand(seed):
    from znicz_tpu.core import prng
    return prng.RandomGenerator().seed(seed)


# -- the scan -----------------------------------------------------------------

def _recurrence(x, dt, a, bm, cm, seg):
    """``S_t = (1 - r_t) exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
    C_t``, position by position, in float64."""
    b, s, h, p = x.shape
    rep = h // bm.shape[2]
    y = numpy.zeros((b, s, h, p))
    for r in range(b):
        state = numpy.zeros((h, p, bm.shape[3]))
        for t in range(s):
            if t and seg[r, t] != seg[r, t - 1]:
                state[...] = 0.0
            big = numpy.repeat(bm[r, t], rep, axis=0)
            state = numpy.exp(dt[r, t] * a)[:, None, None] * state \
                + dt[r, t][:, None, None] * x[r, t][:, :, None] \
                * big[:, None, :]
            y[r, t] = numpy.einsum("hpn,hn->hp", state,
                                   numpy.repeat(cm[r, t], rep, axis=0))
    return y


def _scan_inputs(seed=0, b=2, s=37, h=4, p=3, g=2, n=5):
    rs = numpy.random.RandomState(seed)
    x = rs.normal(size=(b, s, h, p))
    dt = rs.uniform(0.01, 0.5, (b, s, h))
    a = -rs.uniform(0.5, 2.0, h)
    bm, cm = rs.normal(size=(b, s, g, n)), rs.normal(size=(b, s, g, n))
    # row 0: starts at 8 (a chunk's first position at chunks of 4 and 8)
    # and at 21 (inside every chunk but 1 and 3); row 1: one start at 16
    seg = numpy.zeros((b, s), numpy.int32)
    seg[0, 8:], seg[0, 21:], seg[1, 16:] = 1, 2, 3
    return x, dt, a, bm, cm, seg


@pytest.mark.parametrize("chunk", [1, 3, 4, 8, 16, 37, 64])
def test_the_chunked_scan_equals_the_recurrence_token_by_token(chunk):
    x, dt, a, bm, cm, seg = _scan_inputs()
    want = _recurrence(x, dt, a, bm, cm, seg)
    doc = jnp.cumsum(transformer.document_starts(jnp.asarray(seg)), axis=1)
    got = transformer.ssd_scan(*(jnp.asarray(v, jnp.float32)
                                 for v in (x, dt, a, bm, cm)), doc, chunk)
    numpy.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_the_scans_gradient_equals_the_recurrences():
    x, dt, a, bm, cm, seg = _scan_inputs(seed=1, s=20)
    doc = jnp.cumsum(transformer.document_starts(jnp.asarray(seg)), axis=1)
    args = [jnp.asarray(v, jnp.float32) for v in (x, dt, a, bm, cm)]
    weight = jnp.asarray(numpy.random.RandomState(2).normal(
        size=x.shape), jnp.float32)

    def plain(x, dt, a, bm, cm):
        # the same recurrence in jax, token by token, for its gradient
        rep = x.shape[2] // bm.shape[2]
        starts = transformer.document_starts(jnp.asarray(seg))

        def step(state, at):
            x_t, dt_t, b_t, c_t, start = at
            state = jnp.where(start, 0.0, 1.0) * jnp.exp(
                dt_t * a)[:, None, None] * state + dt_t[:, None, None] \
                * x_t[:, :, None] * jnp.repeat(b_t, rep, axis=0)[:, None]
            return state, jnp.einsum("hpn,hn->hp", state,
                                     jnp.repeat(c_t, rep, axis=0))
        rows = [jax.lax.scan(step, jnp.zeros(x.shape[2:] + bm.shape[3:]),
                             (x[r], dt[r], bm[r], cm[r], starts[r]))[1]
                for r in range(x.shape[0])]
        return jnp.stack(rows)

    got = jax.grad(lambda *v: (transformer.ssd_scan(*v, doc, 8) * weight)
                   .sum(), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *v: (plain(*v) * weight).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(("x", "dt", "a", "B", "C"), got, want):
        numpy.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5,
                                      err_msg=name)


def test_the_counts_on_a_known_layout():
    """Chunks of 8 over rows of 37: five a row; starts past a row's first
    position at 8, 21 and 16; only 21 lies inside a chunk."""
    _, _, _, _, _, seg = _scan_inputs()
    starts = transformer.document_starts(jnp.asarray(seg))
    assert transformer.ssd_counts(starts, 8).tolist() == [10, 3, 1]
    # at chunks of 4, 21 is inside chunk 5 and 8, 16 are first positions
    assert transformer.ssd_counts(starts, 4).tolist() == [20, 3, 1]
    # at chunks of 3, 8 and 16 are inside theirs and 21 a first position
    assert transformer.ssd_counts(starts, 3).tolist() == [26, 3, 2]
    one = jnp.asarray(numpy.zeros((1, 16), numpy.int32))
    assert transformer.ssd_counts(transformer.document_starts(one),
                                  8).tolist() == [2, 0, 0]


# -- the mixer ----------------------------------------------------------------

SSD = {"heads": 4, "head_dim": 6, "state": 5, "groups": 2, "conv": 4,
       "chunk": 8, "eps": 1e-5, "weights_stddev": 0.3, "out_stddev": 0.3}


def _mixer(seq=32, seed=7, **more):
    a = dict(SSD, **more)
    spec = transformer.build("ssd", a, (seq, DIM), {}, {}, {})
    rs = numpy.random.RandomState(seed)
    p = {}
    for name, (shape, filling, value, _) in transformer.leaves(spec).items():
        base = {"dt_bias": -1.0, "a_log": 0.5, "d_skip": 1.0,
                "g_norm": 1.0}.get(name, 0.0)
        p[name] = jnp.asarray(rs.normal(base, 0.3, shape), jnp.float32)

    def run(y, seg, p=p):
        ctx = {"cd": None, "segments": jnp.asarray(seg), "train": True,
               "routed": {}, "node": 3}
        return transformer.apply(spec, p, jnp.asarray(y), ctx), ctx

    def plain(y, seg, p=p, fault=None):
        return jnp.stack([ref._ssd(p, jnp.asarray(y[r]), jnp.asarray(seg[r]),
                                   spec.attrs, "f32", fault)
                          for r in range(len(y))])

    return spec, p, run, plain


def _two_documents(seed=9):
    rs = numpy.random.RandomState(seed)
    y = rs.normal(0, 1, (2, 32, DIM)).astype(numpy.float32)
    seg = numpy.ones((2, 32), numpy.int32)
    seg[0, 11:], seg[1, 16:], seg[1, 29:] = 2, 2, 3
    return y, seg


def test_the_mixer_and_its_gradients_equal_the_reference():
    """Output and every leaf's gradient over two rows of two and three
    documents (starts inside chunks of 8 and on their first positions)."""
    spec, p, run, plain = _mixer()
    y, seg = _two_documents()
    out, ctx = run(y, seg)
    numpy.testing.assert_allclose(out, plain(y, seg), rtol=2e-5, atol=2e-6)
    # starts at 11 and 29 inside chunks of 8, at 16 on a first position
    assert ctx["routed"][3]["ssd"].tolist() == [8, 3, 2]
    weight = jnp.asarray(numpy.random.RandomState(3).normal(
        size=out.shape), jnp.float32)
    got = jax.grad(lambda p: (run(y, seg, p)[0] * weight).sum())(p)
    want = jax.grad(lambda p: (plain(y, seg, p) * weight).sum())(p)
    assert set(got) == {"w_in", "w_conv", "b_conv", "dt_bias", "a_log",
                        "d_skip", "g_norm", "w_out"}
    for name in p:
        # (gradients up to 20 here, summed over the row)
        numpy.testing.assert_allclose(got[name], want[name], rtol=2e-4,
                                      atol=5e-5, err_msg=name)
        assert float(jnp.abs(got[name]).max()) > 1e-3, name


@pytest.mark.parametrize("fault", ["reset_left_out", "conv_across_documents",
                                   "norm_whole_width", "d_skip_left_out"])
def test_each_of_the_mixers_faults_moves_its_output(fault):
    _, p, run, plain = _mixer()
    y, seg = _two_documents()
    gap = float(jnp.abs(run(y, seg)[0] - plain(y, seg, fault=fault)).max())
    assert gap > 1e-3 * float(jnp.abs(plain(y, seg)).max()), fault


def test_a_row_of_two_documents_equals_each_document_alone():
    """Neither the state nor the conv crosses a start: a document's outputs
    are the same wherever it lies in the row and whatever stands before it,
    at a start on a chunk's first position (16) and inside one (11)."""
    _, _, run, _ = _mixer()
    rs = numpy.random.RandomState(4)
    first, second = (rs.normal(0, 1, (1, n, DIM)).astype(numpy.float32)
                     for n in (11, 21))
    row = numpy.concatenate([first, second], axis=1)
    seg = numpy.array([[1] * 11 + [2] * 21], numpy.int32)
    whole = run(row, seg)[0]
    numpy.testing.assert_allclose(
        whole[:, :11], run(first, numpy.ones((1, 11), numpy.int32))[0],
        rtol=2e-5, atol=2e-6)
    numpy.testing.assert_allclose(
        whole[:, 11:], run(second, numpy.ones((1, 21), numpy.int32))[0],
        rtol=2e-5, atol=2e-6)
    other = rs.normal(0, 1, (1, 16, DIM)).astype(numpy.float32)
    later = numpy.concatenate([other, second[:, :16]], axis=1)
    seg = numpy.array([[5] * 16 + [6] * 16], numpy.int32)
    numpy.testing.assert_allclose(
        run(later, seg)[0][:, 16:],
        run(second[:, :16], numpy.ones((1, 16), numpy.int32))[0],
        rtol=2e-5, atol=2e-6)


def test_heads_the_groups_do_not_divide_are_refused():
    with pytest.raises(ValueError, match="5 heads over 2 groups"):
        _mixer(heads=5)


# -- the initial draw and the optimizer ---------------------------------------

def _small(**more):
    kw = dict(pattern="MEM*E", vocab=48, dim=DIM, ssd_heads=4,
              ssd_head_dim=6, state=5, groups=2, chunk=8, heads=4,
              kv_heads=2, head_dim=6, experts=8, top_k=2, held_count=8,
              hidden=12, shared_hidden=20, out_stddev=0.01, q_block=8,
              token_block=16)
    kw.update(more)
    return hybrid_moe_lm.make_layers(**kw)


def test_the_program_draws_what_the_reference_draws():
    """Every leaf of every layer, the mixers' uniform, log-uniform and
    inverse-softplus draws included, bit for bit."""
    layers = _small(held_first=2, held_count=4)
    specs = fused.build_specs(layers, (32,))
    got = fused.init_params(specs, _rand(5), numpy.float32)
    want = ref.init_params(layers, 5)
    assert [sorted(p) for p in got] == [sorted(p) for p in want]
    for i, (a, b) in enumerate(zip(got, want)):
        for name in a:
            numpy.testing.assert_array_equal(a[name], b[name],
                                             err_msg="%d.%s" % (i, name))
    mixer = next(p for s, p in zip(specs, got) if s.kind == "ssd")
    a_ = -numpy.exp(mixer["a_log"])
    assert ((-16 <= a_) & (a_ <= -1)).all()
    step = numpy.log1p(numpy.exp(mixer["dt_bias"]))
    assert ((0.001 <= step * 1.0001) & (step <= 0.1 * 1.0001)).all()
    assert (numpy.abs(mixer["w_conv"]) <= 0.5).all()
    assert float(mixer["w_out"].std()) == pytest.approx(0.01, rel=0.1)


def test_a_log_d_dt_bias_the_conv_bias_and_the_gain_take_no_decay():
    layers = _small()
    specs = fused.build_specs(layers, (32,))
    spec = next(s for s in specs if s.kind == "ssd")
    hy = transformer.leaf_hypers(spec)
    for name in ("a_log", "d_skip", "dt_bias", "b_conv", "g_norm"):
        assert hy[name]["wd"] == 0.0, name
    for name in ("w_in", "w_conv", "w_out"):
        assert hy[name]["wd"] == pytest.approx(0.1), name
    mine = ref.hypers(layers)[specs.index(spec)]
    assert {n: h["wd"] for n, h in mine.items()} == {
        n: pytest.approx(h["wd"]) for n, h in hy.items()}


def _row_inputs(layers, seed=4):
    rs = numpy.random.RandomState(seed)
    seg = numpy.repeat(numpy.arange(1, 4), [11, 13, 8]).astype(numpy.int32)
    ids = rs.randint(0, 48, 32).astype(numpy.int32)
    labels = numpy.where(rs.rand(32) < 0.8, rs.randint(0, 48, 32), -1)
    params = ref.init_params(layers, 3)
    for p in params:
        if "sb" in p:
            # a bias the balance has moved, so that the choice reads it
            p["sb"] = rs.normal(0, 0.3, p["sb"].shape).astype(numpy.float32)
    return [jax.tree.map(jnp.asarray, params)] + [
        jnp.asarray(v) for v in (ids, seg, labels.astype(numpy.int32),
                                 numpy.arange(0, 32, 4, dtype=numpy.int32))]


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS
                                   if f != "centring_left_out"])
def test_a_fault_chosen_at_run_time_is_the_fault_named(fault):
    """A reading's program plants its fault by an index it is handed: the
    loss, the logits and every gradient are those of the fault named when
    the row is traced."""
    layers = _small()
    params, ids, seg, labels, sample = _row_inputs(layers)

    def loss(params, fault):
        ce, _, z, _ = ref.forward_row(params, ids, seg, labels, sample,
                                      layers, None, "f32", fault)
        return jnp.where(labels >= 0, ce, 0.0).sum() + 1e-3 * z.sum()

    named = jax.jit(jax.value_and_grad(lambda p: loss(p, fault)))(params)
    chosen = jax.jit(jax.value_and_grad(loss))(
        params, jnp.int32(ref.FAULTS.index(fault)))
    sound = jax.jit(jax.value_and_grad(lambda p: loss(p, None)))(params)
    assert float(abs(named[0] - sound[0])) > 1e-6
    # two programs of the same sums, fused apart: float32 rounding
    for got, want in zip(jax.tree.leaves(chosen), jax.tree.leaves(named)):
        numpy.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_the_readings_of_one_mode_share_one_program():
    """The reference proper is a program of its own; every fault and the
    control's mode reuse theirs, and a row of them plants the fault it
    names (-1 plants none)."""
    layers = _small()
    params, ids, seg, labels, sample = _row_inputs(layers)
    rows = {f: ref.make_row(layers, "bf16", f) for f in (None,) + ref.FAULTS}
    ref.make_row(layers)
    ref.make_row(layers, forced=True)
    assert sorted(k[1:] for k in ref._PROGRAMS
                  if k[0] == ref._key(layers)) == [("bf16", True),
                                                   ("f32", False)]
    zeros = jax.tree.map(jnp.zeros_like, params)
    sums = {f: float(row(params, jax.tree.map(jnp.copy, zeros), ids, seg,
                         labels, sample)[1]["loss_sum"])
            for f, row in rows.items()}
    assert sums["d_skip_left_out"] != sums[None]
    assert sums["centring_left_out"] == sums[None]


# -- the experts --------------------------------------------------------------

EXPERTS, TOP_K, HIDDEN, SHARED = 32, 6, 12, 20


def _moe_attrs(held):
    return {"router": "r", "experts": EXPERTS, "top_k": TOP_K,
            "held": list(held), "hidden": HIDDEN, "shared_hidden": SHARED,
            "gated": False, "activation": "relu2", "score": "sigmoid",
            "route_scale": 2.5, "balance_rate": 1e-3}


def _moe_layers(held):
    bwd = {"learning_rate": 1e-3, "solvers": ["adamw"]}
    return [{"type": "embedding", "name": "embed",
             "->": {"vocab": 8, "dim": DIM}, "<-": bwd},
            {"type": "moe", "->": _moe_attrs(held), "<-": bwd},
            {"type": "lm_head", "name": "head", "->": {"vocab": 8},
             "<-": bwd}]


def _expert_inputs():
    rs = numpy.random.RandomState(4)
    y = jnp.asarray(rs.normal(0, 1, (64, DIM)), jnp.float32)
    logits = jnp.asarray(rs.normal(0, 1, (64, EXPERTS)), jnp.float32)
    bias = rs.normal(0, 0.3, EXPERTS).astype(numpy.float32)
    return y, logits, bias


def test_relu2_experts_and_the_shared_expert_equal_the_reference():
    """Non-gated relu^2 experts, a share of 8 of 32, and the shared expert:
    the output and the gradients of every leaf the experts read."""
    y, logits, bias = _expert_inputs()
    specs = fused.build_specs(_moe_layers((8, 8)), (64,))
    assert set(transformer.leaves(specs[1])) == {"wu", "wd", "su", "sd",
                                                 "sb"}
    p = dict(jax.tree.map(jnp.asarray, fused.init_params(
        specs, _rand(11), numpy.float32)[1]), sb=jnp.asarray(bias))

    def program(p):
        ctx = {"cd": None, "side": {"r": logits}, "routed": {}, "node": 1}
        return transformer.apply(specs[1], p, y[None], ctx)[0]

    def plain(p, fault=None):
        return ref._moe(p, y, logits, _moe_attrs((8, 8)), None, "f32",
                        fault)[0]

    numpy.testing.assert_allclose(program(p), plain(p), rtol=2e-5,
                                  atol=2e-6)
    got = jax.grad(lambda p: (program(p) ** 2).sum())(p)
    want = jax.grad(lambda p: (plain(p) ** 2).sum())(p)
    for name in ("wu", "wd", "su", "sd"):
        numpy.testing.assert_allclose(got[name], want[name], rtol=2e-4,
                                      atol=2e-7, err_msg=name)
    for fault in ("relu_not_squared", "shared_left_out"):
        assert float(jnp.abs(program(p) - plain(p, fault)).max()) > 1e-4


def test_sixteen_shares_of_the_expert_layer_add_up_to_the_uncut_one():
    """Top-6 of 32 by sigmoid scores plus a bias, scale 2.5: the routed
    parts of sixteen shares of two experts each, every one drawn by the
    program as it draws its share, plus the shared expert counted ONCE,
    equal what the uncut reference's layer gives from its own draw."""
    y, logits, bias = _expert_inputs()
    whole = ref.init_params(_moe_layers((0, EXPERTS)), 11)[1]
    whole["sb"] = bias
    want, report = ref._moe(jax.tree.map(jnp.asarray, whole), y, logits,
                            _moe_attrs((0, EXPERTS)), None, "f32", None)
    routed, once = 0.0, None
    for first in range(0, EXPERTS, 2):
        specs = fused.build_specs(_moe_layers((first, 2)), (64,))
        p = fused.init_params(specs, _rand(11), numpy.float32)[1]
        for name in ("wu", "wd"):
            numpy.testing.assert_array_equal(p[name],
                                             whole[name][first:first + 2])
        for name in ("su", "sd"):
            numpy.testing.assert_array_equal(p[name], whole[name])
        p = dict(jax.tree.map(jnp.asarray, p), sb=jnp.asarray(bias))
        ctx = {"cd": None, "side": {"r": logits}, "routed": {}, "node": 1}
        out = transformer.apply(specs[1], p, y[None], ctx)[0]
        once = ref._expert(y, p["su"], p["sd"], True, "f32")
        routed = routed + (out - once)
        numpy.testing.assert_array_equal(ctx["routed"][1]["load"],
                                         report["load"])
    numpy.testing.assert_allclose(routed + once, want, rtol=2e-5, atol=2e-6)
    assert int(report["load"].sum()) == 64 * TOP_K


def test_expert_tiles_of_a_width_that_is_no_multiple_of_128():
    """The grouped kernel's tiles: the largest multiple of 128 dividing a
    width (as before), and of 1,856 (the published experts) 640, which
    tiles the width rounded up to 1,920 in three."""
    assert transformer._tile(2688, 1280) == 896
    assert transformer._tile(49152, 512) == 512
    assert transformer._tile(1536, 1280) == 768
    assert transformer._tile(1856, 1280) == 640



@pytest.mark.parametrize("name", ["smallthinker_21b_a3b", "trinity_mini",
                                  "glm_4_7_flash"])
def test_the_accepted_expert_cells_tile_as_before(name, monkeypatch):
    """Every side of every grouped product of the benchmark's other expert
    configurations, traced at their own widths, is a multiple of 128, so
    their kernel takes the tiles it took before widths that are not
    (largest multiple of 128 that divides, up to the kernel's most)."""
    import importlib
    seen = []

    def grouped(x, w, sizes):
        seen.append((x.shape[0], x.shape[1], w.shape[2]))
        return jax.lax.ragged_dot(x, w, sizes[:w.shape[0]])

    monkeypatch.setattr(transformer, "grouped_dot", grouped)
    cfg = json.load(open(os.path.join(os.path.dirname(TESTS), os.pardir,
                                      "configs", name + ".json")))
    made = cfg["layers_from"]
    layers = getattr(importlib.import_module(cfg["sample"]), made["call"])(
        **dict(zip(made["arg_names"], made["args"])))
    spec = next(s for s in fused.build_specs(layers, (8192,))
                if s.kind == "moe")
    p = {k: jax.ShapeDtypeStruct(v[0], jnp.bfloat16)
         for k, v in transformer.leaves(spec).items()}
    width, experts = p["wd"].shape[-1], int(spec.attrs["experts"])

    def apply(p, y, r):
        ctx = {"cd": jnp.bfloat16, "side": {spec.attrs["router"]: r},
               "routed": {}, "node": 1, "train": True}
        return transformer.apply(spec, p, y, ctx)

    jax.eval_shape(apply, p,
                   jax.ShapeDtypeStruct((1, 8192, width), jnp.bfloat16),
                   jax.ShapeDtypeStruct((8192, experts), jnp.float32))
    assert len(seen) == 3
    for sides in seen:
        for n, most in zip(sides, transformer.GMM_TILES):
            assert n % transformer.KERNEL_TILE == 0, (sides, n)
            assert transformer._tile(n, most) == max(
                t for t in range(128, min(most, n) + 1, 128) if n % t == 0)


# -- the sample and the tiny cell ---------------------------------------------

def test_the_pattern_lays_out_the_layers():
    layers = hybrid_moe_lm.make_layers(pattern="MEMEM*EME", first=2,
                                       count=5)
    kinds = [layer["layers"][-1]["type"] for layer in layers
             if layer["type"] == "residual"]
    assert kinds == ["ssd", "moe", "ssd", "attention", "moe"]
    assert [layer["layers"][0]["name"] for layer in layers
            if layer["type"] == "residual"] == [
        "l%d_norm" % i for i in range(2, 7)]
    attn = layers[4]["layers"][-1]["->"]
    assert attn["rope"] is False
    with pytest.raises(ValueError, match="M, E and \\* only"):
        hybrid_moe_lm.make_layers(pattern="M-E")


@pytest.fixture(scope="module")
def ssd_run():
    """The fixture cell end to end: StandardWorkflow, fused trainer,
    evaluator, decision; the reference follows the first epoch's four
    steps under the program's choice of experts."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.config import root
    was = root.common.telemetry.get("enabled", False)
    loader_was = root.hybrid_moe_lm.loader_name
    telemetry.enable()
    telemetry.reset()
    try:
        correct, nums = rehearse.tiny_cell(
            CELL, _load("configs", CELL["config"] + ".json"),
            _load("traffic", CELL["traffic"] + ".json"),
            _load("limits", CELL["name"] + ".json"))
        counters = {n: telemetry.counter(n).value for n in (
            "ssd.chunks", "ssd.resets", "ssd.chunks_split",
            "ssd.chunks_kernel", "ssd.conv_kernel", "trainer.rows",
            "attention.forwards_kept")}
    finally:
        root.common.telemetry.enabled = was
        root.hybrid_moe_lm.loader_name = loader_was
    return correct, {n: (v, lim) for n, v, lim in nums}, counters


@pytest.mark.parametrize("name", NUMBERS)
def test_ssd_window_equals_the_plain_reference(ssd_run, name):
    """The loss of every step and the logits at the sampled positions,
    every leaf's first moment and parameter change over four AdamW steps,
    the bias after them, under the program's choice; every step's load and
    the graded tokens exactly."""
    _, nums, _ = ssd_run
    value, limit = nums[name]
    assert numpy.isfinite(value) and value <= limit, (name, value, limit)


def test_ssd_cell_is_correct_and_counts_its_chunks(ssd_run):
    """Two mixers a row of 32 at chunks of 8: eight chunks a row, read at
    the window's readback; the documents start inside rows and chunks."""
    correct, _, counters = ssd_run
    assert correct
    rows = counters["trainer.rows"]
    assert rows > 0 and counters["ssd.chunks"] == 2 * 4 * rows
    assert 0 < counters["ssd.chunks_split"] <= counters["ssd.resets"]
    assert counters["attention.forwards_kept"] == 0


def test_no_chunk_runs_as_the_kernel_off_the_chip(ssd_run):
    """On the CPU the scan is the ``jax.numpy`` form: the kernels' count
    of chunks reads 0 beside the chunks scanned."""
    _, _, counters = ssd_run
    assert counters["ssd.chunks"] > 0
    assert counters["ssd.chunks_kernel"] == 0


def test_no_conv_runs_as_the_kernel_off_the_chip(ssd_run):
    """On the CPU the conv is the ``jax.numpy`` form: the kernels' count
    of conv applications reads 0 beside the rows stepped."""
    _, _, counters = ssd_run
    assert counters["trainer.rows"] > 0
    assert counters["ssd.conv_kernel"] == 0


def test_the_conv_kernel_counts_a_row_a_mixer_where_built_for_a_tpu():
    """The host count ``FusedNet.ssd_conv_kernel`` is the mixers whose
    widths tile (channels from column 128, 256 of them; rows of 32) where
    the net is built for a TPU, and 0 on the CPU; a train readback adds it
    times the rows stepped to ``ssd.conv_kernel``."""
    import types
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.config import root
    from znicz_tpu.units.fused_trainer import FusedForwardBackward
    layers = hybrid_moe_lm.make_layers(pattern="MEM", ssd_heads=2,
                                       ssd_head_dim=64, state=64, groups=1)
    with transformer.lowering_for("tpu"):
        mixers = fused.FusedNet(layers, (32,), objective="tokens") \
            .ssd_conv_kernel
    assert mixers == 2
    assert fused.FusedNet(layers, (32,), objective="tokens") \
        .ssd_conv_kernel == 0
    unit = types.SimpleNamespace(net=types.SimpleNamespace(
        ssd_kernel=False, ssd_conv_kernel=mixers))
    host = {"n_err": numpy.asarray([0, 96, 3]), "loss_sum": 1.0,
            "ssd_counts": numpy.asarray([24, 2, 1])}
    was = root.common.telemetry.get("enabled", False)
    telemetry.enable()
    telemetry.reset()
    try:
        FusedForwardBackward._set_token_stats(unit, host, True)
        assert telemetry.counter("ssd.conv_kernel").value == 2 * 3
        assert telemetry.counter("ssd.chunks_kernel").value == 0
    finally:
        root.common.telemetry.enabled = was


def test_the_published_widths_count_the_published_parameters():
    """The catalog's widths under these equations: 31.58 B parameters for
    the 52 published layers, as the catalog's 31.6 B; a cut of layers 0-8
    at 8 experts and a vocabulary of 16,384 is 666,963,456."""
    def count(pattern, experts, vocab):
        layers = hybrid_moe_lm.make_layers(
            pattern=pattern, vocab=vocab, dim=2688, ssd_heads=64,
            ssd_head_dim=64, state=128, groups=8, chunk=128, heads=32,
            kv_heads=2, head_dim=128, experts=128, top_k=6,
            held_count=experts, hidden=1856, shared_hidden=3712)
        specs = fused.build_specs(layers, (128,))
        return sum(math.prod(leaf[0]) for spec in specs
                   for leaf in transformer.leaves(spec).values())
    published = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert count(published, 128, 131072) == pytest.approx(31.58e9,
                                                          rel=5e-4)
    assert count(published[:9], 8, 16384) == 666963456
