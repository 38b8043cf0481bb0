"""Off-chip compiles for a described TPU v5e (2x2): what the chip's
compiler would say to the programs of the main path, without a chip.

The TPU compiler is installed beside jax and compiles for a topology that
is described, not attached (``on-chip-measurement`` guide, section 2).
These cases guard what interpret mode and the CPU backend cannot see —
a Pallas kernel the Mosaic compiler refuses (tiling, VMEM), a step program
that does not fit or lower — at the widths ``chip_smoke.py`` runs.
Nothing executes: a case that passes is a compile, not
a chip run.  Skipped where the topology cannot be described.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from znicz_tpu.core.config import root  # noqa: E402
from znicz_tpu.parallel import fused  # noqa: E402


@pytest.fixture(scope="module")
def chip():
    """One described v5e device's sharding; the persistent compilation
    cache is off around the module (an entry compiled for a described
    chip is written but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip("cannot describe a v5e:2x2 topology: %r" % (e,))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _described(tree, sharding):
    """Shapes of ``tree``'s arrays, placed on the described device."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(numpy.shape(a), a.dtype,
                                       sharding=sharding), tree)


#: (input shape, kernel, stride): both pools of the MNIST conv flagship,
#: cifar-caffe's pool1 at bench width and at chip_smoke's unit-graph batch
POOL_CASES = [
    ((128, 24, 24, 64), 2, 2),
    ((128, 8, 8, 87), 2, 2),
    ((128, 32, 32, 32), 3, 2),
    ((100, 32, 32, 32), 3, 2),
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape,k,stride", POOL_CASES)
def test_pallas_max_pool_compiles_for_v5e(chip, shape, k, stride, dtype):
    """The kernel ``supported()`` sends to the chip is one Mosaic
    accepts: an unaligned slice or a VMEM overrun fails here, not as a
    quiet switch of lowering on the chip."""
    from znicz_tpu.ops import pallas_pooling
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=chip)
    assert pallas_pooling.supported(x, k, k, (stride, stride), False)
    compiled = pallas_pooling.max_pooling_offsets_pallas.lower(
        x, k, k, (stride, stride)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _train_step_program(layers, sample_shape, batch, chip):
    specs = fused.build_specs([dict(l) for l in layers], sample_shape)
    params = fused.init_params(specs)
    state = fused.init_opt_state(specs, params)
    x = jax.ShapeDtypeStruct((batch,) + tuple(sample_shape), jnp.bfloat16,
                             sharding=chip)
    labels = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=chip)

    def step(p, s, x, labels):
        return fused._train_step(p, s, x, labels, tuple(specs),
                                 compute_dtype=jnp.bfloat16)

    return jax.jit(step).lower(_described(params, chip),
                               _described(state, chip), x, labels)


def _cifar_layers():
    import znicz_tpu.samples.cifar  # noqa: F401 (root.cifar)
    return root.cifar.layers


def _flagship_layers():
    import znicz_tpu.samples.mnist  # noqa: F401 (root.mnistr_conv)
    return root.mnistr_conv.layers


@pytest.mark.parametrize("layers,sample_shape,batch", [
    pytest.param(_cifar_layers, (32, 32, 3), 32, id="cifar_caffe-b32"),
    pytest.param(_cifar_layers, (32, 32, 3), 4096, id="cifar_caffe-b4096",
                 marks=pytest.mark.slow),
    pytest.param(_flagship_layers, (28, 28, 1), 16384,
                 id="mnist_conv-b16384", marks=pytest.mark.slow),
])
def test_fused_train_step_compiles_for_v5e(chip, layers, sample_shape,
                                           batch):
    """One bf16 fused train step (forward, backward, update) at the
    model's published widths — the batch is what the fast tier cuts
    (to 32: the compile takes 6 s there, 12-30 s at 64-4096)."""
    compiled = _train_step_program(layers(), sample_shape, batch,
                                   chip).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 16 << 30


def test_serving_forward_compiles_for_v5e(chip, tmp_path):
    """The forward ``serve --latest`` builds from a fused-mode snapshot
    of cifar-caffe, at the default ladder's largest bucket (64)."""
    from znicz_tpu.core.backends import JaxDevice
    from znicz_tpu.samples import cifar
    from znicz_tpu.serving.engine import InferenceEngine
    wf = cifar.build(
        loader_config={"minibatch_size": 20, "synthetic": True,
                       "synthetic_train": 40, "synthetic_valid": 20},
        snapshotter_config={"directory": str(tmp_path)},
        fused={"window": 2})
    wf.initialize(device=JaxDevice())
    engine = InferenceEngine(wf.snapshotter.export(), warmup=False)
    assert engine.buckets[-1] == 64
    model = engine._model
    x = jax.ShapeDtypeStruct((64,) + model.sample_shape, model.dtype,
                             sharding=chip)
    compiled = model.fn.lower(_described(model.params, chip), x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


def test_looped_lm_window_with_the_kernel_compiles_for_v5e(chip):
    """A looped language model's train window (two layers, two passes,
    a head of 128, two rows of 1,024 tokens a step, bf16) with attention as
    the TPU's splash-attention kernel under each row's own block map:
    Mosaic takes its blocks and the traced maps, and the window with the
    loop's scan, the recomputation and the blocked head lowers."""
    from znicz_tpu.ops import transformer
    from znicz_tpu.samples.research import looped_lm
    layers = looped_lm.make_layers(
        vocab=1024, dim=256, heads=2, kv_heads=2, head_dim=128, hidden=512,
        n_layers=2, passes=2, q_block=512, token_block=512)
    specs = fused.build_specs(layers, (1024,))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = [{n: sds(s[0], jnp.float32)
               for n, s in transformer.leaves(sp).items()} for sp in specs]
    state = [{n: {"m": a, "v": a, "t": sds((), jnp.float32)}
              for n, a in p.items()} for p in params]
    real_init, real_put = fused.init_params, jax.device_put
    fused.init_params = lambda specs, rand, dtype: [
        {n: numpy.zeros((1,), numpy.float32) for n in p} for p in params]
    jax.device_put = lambda x, *a, **kw: x
    try:
        net = fused.FusedNet(layers, (1024,), compute_dtype=jnp.bfloat16,
                             objective="tokens")
    finally:
        fused.init_params, jax.device_put = real_init, real_put
    k, batch, rows = 2, 2, 6
    hy = jax.tree.map(lambda v: sds((k,), jnp.float32),
                      fused.default_hypers(net.specs))
    acc = {n: sds(v.shape, v.dtype)
           for n, v in net.window_acc_zeros().items()}
    data = sds((rows, 1024), jnp.int32)
    # the library kernel's index arithmetic is int32 and its products take
    # the chip's default precision: x64 and ``highest`` (both on in the
    # tests) are off around it, as they are on the chip
    with jax.enable_x64(False), jax.default_matmul_precision("default"), \
            transformer.lowering_for("tpu"):
        compiled = net._get_window_fn(k, "indexed").lower(
            params, state, sds((2,), jnp.uint32), data, (data, data),
            sds((k, batch), jnp.int32), None, sds((k,), jnp.int32), hy,
            acc).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text        # the kernel is in the program
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def test_routed_lm_window_with_its_kernels_compiles_for_v5e(chip):
    """A routed language model's train window (four layers: one global with
    no position encoding, three rotary under a window of 256; 4 query heads
    of 128 on 2 key-value heads; 8 experts of which the share holds four,
    top-2; rows of 1,024 tokens, bf16): Mosaic takes the splash-attention
    kernel's local and causal masks with grouped heads and the grouped
    matmul's dynamic grid, and the window with the router's side value
    through the recomputed residual entries lowers."""
    from znicz_tpu.ops import transformer
    from znicz_tpu.samples.research import routed_lm
    layers = routed_lm.make_layers(
        vocab=1024, dim=256, heads=4, kv_heads=2, head_dim=128, experts=8,
        top_k=2, held_first=2, held_count=4, hidden=256, n_layers=4,
        window=256, q_block=512, token_block=512)
    specs = fused.build_specs(layers, (1024,))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = [{n: sds(s[0], jnp.float32)
               for n, s in transformer.leaves(sp).items()} for sp in specs]
    state = [{n: {"m": a, "v": a, "t": sds((), jnp.float32)}
              for n, a in p.items()} for p in params]
    real_init, real_put = fused.init_params, jax.device_put
    fused.init_params = lambda specs, rand, dtype: [
        {n: numpy.zeros((1,), numpy.float32) for n in p} for p in params]
    jax.device_put = lambda x, *a, **kw: x
    try:
        net = fused.FusedNet(layers, (1024,), compute_dtype=jnp.bfloat16,
                             objective="tokens")
    finally:
        fused.init_params, jax.device_put = real_init, real_put
    k, batch, rows = 2, 2, 6
    hy = jax.tree.map(lambda v: sds((k,), jnp.float32),
                      fused.default_hypers(net.specs))
    acc = {n: sds(v.shape, v.dtype)
           for n, v in net.window_acc_zeros().items()}
    assert acc["moe_load"].shape == (4, 8)
    data = sds((rows, 1024), jnp.int32)
    with jax.enable_x64(False), jax.default_matmul_precision("default"), \
            transformer.lowering_for("tpu"):
        compiled = net._get_window_fn(k, "indexed").lower(
            params, state, sds((2,), jnp.uint32), data, (data, data),
            sds((k, batch), jnp.int32), None, sds((k,), jnp.int32), hy,
            acc).compile()
    text = compiled.as_text()
    # four layers' attention and three grouped products, forward, recomputed
    # and backward: the kernels are in the program
    assert text.count("tpu_custom_call") >= 4 * (3 + 3 * 3)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def test_shared_moe_lm_window_with_its_kernels_compiles_for_v5e(chip):
    """The train window of a model of shared and routed experts under
    gated, normed attention (one dense and two expert layers, the second of
    the three full with no position and the others rotary under a window of
    256; 4 query heads of 128 on 2 key-value heads; 8 experts of which the
    share holds four, top-2 by sigmoid scores plus the selection bias; rows
    of 1,024 tokens, bf16): the splash kernel takes normed, gated heads,
    the grouped matmul stands beside the shared expert's plain products,
    and the bias, which has no optimizer state, rides through the scan."""
    from znicz_tpu.ops import transformer
    from znicz_tpu.samples.research import shared_moe_lm
    layers = shared_moe_lm.make_layers(
        vocab=1024, dim=256, heads=4, kv_heads=2, head_dim=128,
        dense_hidden=512, experts=8, top_k=2, held_first=2, held_count=4,
        hidden=256, shared_hidden=256, n_layers=3, dense_layers=1,
        window_layout=(1, 0, 1), window=256, q_block=512, token_block=512)
    specs = fused.build_specs(layers, (1024,))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = [{n: sds(s[0], jnp.float32)
               for n, s in transformer.leaves(sp).items()} for sp in specs]
    state = [{n: {"m": a, "v": a, "t": sds((), jnp.float32)}
              if n in transformer.leaf_hypers(sp) else {}
              for n, a in p.items()} for sp, p in zip(specs, params)]
    real_init, real_put = fused.init_params, jax.device_put
    fused.init_params = lambda specs, rand, dtype: [
        {n: numpy.zeros((1,), numpy.float32) for n in p} for p in params]
    jax.device_put = lambda x, *a, **kw: x
    try:
        net = fused.FusedNet(layers, (1024,), compute_dtype=jnp.bfloat16,
                             objective="tokens")
    finally:
        fused.init_params, jax.device_put = real_init, real_put
    k, batch, rows = 2, 2, 6
    hy = jax.tree.map(lambda v: sds((k,), jnp.float32),
                      fused.default_hypers(net.specs))
    acc = {n: sds(v.shape, v.dtype)
           for n, v in net.window_acc_zeros().items()}
    assert acc["moe_load"].shape == (2, 8)
    assert acc["moe_bias_abs_max"].shape == ()
    data = sds((rows, 1024), jnp.int32)
    with jax.enable_x64(False), jax.default_matmul_precision("default"), \
            transformer.lowering_for("tpu"):
        compiled = net._get_window_fn(k, "indexed").lower(
            params, state, sds((2,), jnp.uint32), data, (data, data),
            sds((k, batch), jnp.int32), None, sds((k,), jnp.int32), hy,
            acc).compile()
    text = compiled.as_text()
    # three layers' attention and two layers' three grouped products,
    # forward, recomputed and backward
    assert text.count("tpu_custom_call") >= 3 * 3 + 2 * 3 * 3
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def test_latent_moe_lm_window_with_its_kernels_compiles_for_v5e(chip):
    """The train window of a model of latent attention and shared and
    routed experts with a multi-token prediction module (one dense and one
    expert layer, then the module's layer; 2 heads of 192 + 64 with values
    of 256, the kernel's blocks halved at that width; 8 experts of which
    the share holds four, top-2 by sigmoid scores plus the selection bias;
    two heads, the second reading the first's weight; rows of 1,024
    tokens, bf16): the splash kernel takes the latent heads, the grouped
    matmul the experts, and the second head's counts ride the window."""
    from znicz_tpu.ops import transformer
    from znicz_tpu.samples.research import latent_moe_lm
    layers = latent_moe_lm.make_layers(
        vocab=1024, dim=256, heads=2, q_rank=128, kv_rank=128, nope_dim=192,
        rope_dim=64, v_dim=256, dense_hidden=512, experts=8, top_k=2,
        held_first=2, held_count=4, hidden=256, shared_hidden=256,
        n_layers=2, dense_layers=1, q_block=512, token_block=512)
    specs = fused.build_specs(layers, (1024,))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = [{n: sds(s[0], jnp.float32)
               for n, s in transformer.leaves(sp).items()} for sp in specs]
    state = [{n: {"m": a, "v": a, "t": sds((), jnp.float32)}
              if n in transformer.leaf_hypers(sp) else {}
              for n, a in p.items()} for sp, p in zip(specs, params)]
    real_init, real_put = fused.init_params, jax.device_put
    fused.init_params = lambda specs, rand, dtype: [
        {n: numpy.zeros((1,), numpy.float32) for n in p} for p in params]
    jax.device_put = lambda x, *a, **kw: x
    try:
        net = fused.FusedNet(layers, (1024,), compute_dtype=jnp.bfloat16,
                             objective="tokens")
    finally:
        fused.init_params, jax.device_put = real_init, real_put
    k, batch, rows = 2, 2, 6
    hy = jax.tree.map(lambda v: sds((k,), jnp.float32),
                      fused.default_hypers(net.specs))
    acc = {n: sds(v.shape, v.dtype)
           for n, v in net.window_acc_zeros().items()}
    assert acc["mtp_n_err"].shape == (2,)
    data = sds((rows, 1024), jnp.int32)
    with jax.enable_x64(False), jax.default_matmul_precision("default"), \
            transformer.lowering_for("tpu"):
        compiled = net._get_window_fn(k, "indexed").lower(
            params, state, sds((2,), jnp.uint32), data, (data, data),
            sds((k, batch), jnp.int32), sds((64,), jnp.int32),
            sds((k,), jnp.int32), hy, acc).compile()
    text = compiled.as_text()
    # three layers' attention (forward and its backward's two kernels)
    # and two expert layers' three grouped products, forward, recomputed
    # and backward
    assert text.count("tpu_custom_call") >= 3 * 3 + 2 * 3 * 3
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def _hybrid_window(chip, compile=True):
    """The train window of :func:`test_hybrid_moe_lm_window_with_its_
    kernels_compiles_for_v5e`'s model for the described chip, compiled (or
    only lowered, where not ``compile``)."""
    from znicz_tpu.ops import transformer
    from znicz_tpu.samples.research import hybrid_moe_lm
    layers = hybrid_moe_lm.make_layers(
        pattern="MEM*E", vocab=1024, dim=256, ssd_heads=4, ssd_head_dim=64,
        state=128, groups=2, chunk=128, heads=2, kv_heads=1, head_dim=128,
        experts=8, top_k=2, held_first=2, held_count=4, hidden=192,
        shared_hidden=256, q_block=512, token_block=512)
    specs = fused.build_specs(layers, (1024,))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = [{n: sds(s[0], jnp.float32)
               for n, s in transformer.leaves(sp).items()} for sp in specs]
    state = [{n: {"m": a, "v": a, "t": sds((), jnp.float32)}
              if n in transformer.leaf_hypers(sp) else {}
              for n, a in p.items()} for sp, p in zip(specs, params)]
    real_init, real_put = fused.init_params, jax.device_put
    fused.init_params = lambda specs, rand, dtype: [
        {n: numpy.zeros((1,), numpy.float32) for n in p} for p in params]
    jax.device_put = lambda x, *a, **kw: x
    try:
        net = fused.FusedNet(layers, (1024,), compute_dtype=jnp.bfloat16,
                             objective="tokens")
    finally:
        fused.init_params, jax.device_put = real_init, real_put
    k, batch, rows = 2, 2, 6
    hy = jax.tree.map(lambda v: sds((k,), jnp.float32),
                      fused.default_hypers(net.specs))
    acc = {n: sds(v.shape, v.dtype)
           for n, v in net.window_acc_zeros().items()}
    assert acc["ssd_counts"].shape == (3,)
    data = sds((rows, 1024), jnp.int32)
    with jax.enable_x64(False), jax.default_matmul_precision("default"), \
            transformer.lowering_for("tpu"):
        lowered = net._get_window_fn(k, "indexed").lower(
            params, state, sds((2,), jnp.uint32), data, (data, data),
            sds((k, batch), jnp.int32), None, sds((k,), jnp.int32), hy,
            acc)
        return lowered.compile() if compile else lowered


@pytest.fixture(scope="module")
def hybrid_window(chip):
    return _hybrid_window(chip)


def test_hybrid_moe_lm_window_with_its_kernels_compiles_for_v5e(
        hybrid_window):
    """The train window of a model of Mamba-2 mixers, attention and relu^2
    experts (``MEM*E``; mixers of 4 heads of 64, state 128 in 2 groups,
    chunks of 128; 2 query heads of 128 on one key-value head with no
    position; 8 experts of which the share holds four, top-2, 192 wide,
    which is no multiple of the kernel's 128; rows of 1,024 tokens, bf16):
    the grouped matmul takes the experts' uneven width forward and
    backward, the splash kernel the attention, the scan's and the conv's
    kernels the mixers, and the scan's counts ride the window."""
    compiled = hybrid_window
    text = compiled.as_text()
    # one layer's attention and two layers' two grouped products, forward,
    # recomputed and backward
    assert text.count("tpu_custom_call") >= 3 + 2 * 2 * 3
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


#: a ``tpu_custom_call`` of the compiled text and its ``op_name``
CUSTOM_CALL = re.compile(r'custom_call_target="tpu_custom_call".*?'
                         r'op_name="([^"]*)"')


def test_the_scan_kernels_stand_in_the_window_under_the_scan_scope(
        chip, hybrid_window, monkeypatch):
    """The same window with the mixers' scans as the ``jax.numpy`` form
    and as the kernels (``transformer.ssd_kernel_suits``, which takes these
    widths for a TPU): four kernels a mixer come in (the forward in the
    step's forward and in the residual entry's recomputation, the states
    the backward reads, the backward), nothing else changes (counted in
    the two programs as lowered), and every one of them stands under its
    mixer's ``L*.ssd_scan`` scope in the compiled program, backward too,
    where the benchmark's ``ssd_scan_device_ms_per_step`` reads it."""
    from benchmarks.lib import scoped_trace
    from znicz_tpu.ops import transformer
    with_kernels = _hybrid_window(chip, compile=False).as_text()
    monkeypatch.setattr(transformer, "ssd_kernel_suits", lambda *a: False)
    jax.clear_caches()      # the stage's checkpoint keeps its trace
    try:
        without = _hybrid_window(chip, compile=False).as_text()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert with_kernels.count("tpu_custom_call") \
        - without.count("tpu_custom_call") \
        == with_kernels.count("ssd_scan_") == 2 * 4
    assert "ssd_scan_" not in without
    scan = [n for n in CUSTOM_CALL.findall(hybrid_window.as_text())
            if "/ssd_scan_" in n]
    assert len(scan) == 2 * 4
    for kind in ("forward", "states", "backward"):
        assert any("/ssd_scan_%s/" % kind in n for n in scan), kind
    for n in scan:
        scope, _ = scoped_trace.scope_of(n)
        assert scoped_trace.kind_of(scope) == "ssd_scan", n


def test_the_conv_kernels_stand_in_the_window_under_the_conv_scope(
        chip, hybrid_window, monkeypatch):
    """The same window with the mixers' convs as the ``jax.numpy`` form
    and as the kernels (``transformer.conv_kernel_suits``, which takes
    these widths for a TPU): three kernels a mixer come in (the forward in
    the step's forward and in the residual entry's recomputation, the
    backward; the stage's own recomputed forward is dead), nothing else
    changes (counted in the two programs as lowered), and every one of
    them stands under its mixer's ``L*.ssd_conv`` scope in the compiled
    program, backward too, where the benchmark's
    ``ssd_device_ms_per_step`` reads it."""
    from benchmarks.lib import scoped_trace
    from znicz_tpu.ops import transformer
    with_kernels = _hybrid_window(chip, compile=False).as_text()
    monkeypatch.setattr(transformer, "conv_kernel_suits", lambda *a: False)
    jax.clear_caches()      # the stage's checkpoint keeps its trace
    try:
        without = _hybrid_window(chip, compile=False).as_text()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert with_kernels.count("tpu_custom_call") \
        - without.count("tpu_custom_call") \
        == with_kernels.count("ssd_conv_") == 2 * 3
    assert "ssd_conv_" not in without
    conv = [n for n in CUSTOM_CALL.findall(hybrid_window.as_text())
            if "/ssd_conv_" in n]
    assert len(conv) == 2 * 3
    for kind in ("forward", "backward"):
        assert any("/ssd_conv_%s/" % kind in n for n in conv), kind
    for n in conv:
        scope, _ = scoped_trace.scope_of(n)
        assert scoped_trace.kind_of(scope) == "ssd_conv", n


#: a small conv net over a resident set of bf16 images with 3 channels
#: last, the shape family of AlexNet's bf16[8448,227,227,3]
SET_SHAPE, SET_MINIBATCH = (512, 32, 32, 3), 64
SET_LAYERS = [
    {"type": "conv_relu", "->": {"n_kernels": 16, "kx": 3, "ky": 3},
     "<-": {"learning_rate": 0.01}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.01}},
]
#: an op that copies an array of the whole set's shape
SET_COPY = re.compile(r"= bf16\[%s\]\{[^}]*\} copy\("
                      % ",".join(map(str, SET_SHAPE)))


def _gathering_program(program, data, chip):
    """``program`` of the small conv net, gathering its rows from the
    resident set described by ``data``, compiled for the described chip:
    the compiled program and the format it takes the set in."""
    net = fused.FusedNet(SET_LAYERS, SET_SHAPE[1:],
                         compute_dtype=jnp.bfloat16)
    params = _described(net.params, chip)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    if program == "validation":
        compiled = net._fwd_idx_at.lower(
            params, data, sds((SET_MINIBATCH,), jnp.int32), None).compile()
        return compiled, compiled.input_formats[0][1]
    k = 2
    hy = jax.tree.map(lambda v: sds((k,), jnp.float32),
                      fused.default_hypers(net.specs))
    compiled = net._get_window_fn(k, "indexed").lower(
        params, _described(net.state, chip), sds((2,), jnp.uint32), data,
        sds(SET_SHAPE[:1], jnp.int32), sds((k, SET_MINIBATCH), jnp.int32),
        None, sds((k,), jnp.int32), hy,
        _described(net.window_acc_zeros(), chip)).compile()
    return compiled, compiled.input_formats[0][3]


@pytest.mark.parametrize("program", ["train_window", "validation"])
def test_a_set_in_the_gather_format_is_not_copied(chip, program):
    """The resident set described in the format ``set_dataset`` stores it
    in (the compiler's answer for the row gather's operand): the program
    takes it as it is and copies no array of the set's shape."""
    fmt, _ = fused.gather_format(SET_SHAPE, jnp.bfloat16, chip,
                                 SET_MINIBATCH)
    data = jax.ShapeDtypeStruct(SET_SHAPE, jnp.bfloat16, sharding=fmt)
    compiled, taken = _gathering_program(program, data, chip)
    assert taken.layout == fmt.layout
    assert not SET_COPY.search(compiled.as_text())


@pytest.mark.parametrize("program", ["train_window", "validation"])
def test_a_set_in_the_default_layout_is_copied_whole(chip, program):
    """Why ``set_dataset`` relays the set: left in the runtime's default
    layout (the row index in the lanes) every program that gathers from
    it copies it whole first.  The day this fails the compiler no longer
    needs the cure, and ``gather_format`` with its relayout can go."""
    data = jax.ShapeDtypeStruct(SET_SHAPE, jnp.bfloat16, sharding=chip)
    compiled, taken = _gathering_program(program, data, chip)
    fmt, default = fused.gather_format(SET_SHAPE, jnp.bfloat16, chip,
                                       SET_MINIBATCH)
    assert taken.layout == default.layout != fmt.layout
    assert SET_COPY.search(compiled.as_text())


def test_token_rows_keep_the_default_layout(chip):
    """Two-dimensional integer rows (the token cell's ids): the compiler's
    answer for the gather is the layout the runtime places by default, so
    ``set_dataset`` has nothing to relay, there as here on the CPU."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.samples.research import looped_lm
    shape, rows = (10, 4096), 2
    fmt, default = fused.gather_format(shape, jnp.int32, chip, rows)
    assert fmt.layout == default.layout
    net = fused.FusedNet(
        looped_lm.make_layers(vocab=64, dim=16, heads=2, kv_heads=2,
                              head_dim=8, hidden=32, n_layers=1, passes=2),
        (32,), compute_dtype=jnp.bfloat16, objective="tokens")
    ids = numpy.zeros((10, 32), numpy.int32)
    was = root.common.telemetry.get("enabled", False)
    root.common.telemetry.enabled = True
    telemetry.reset()
    try:
        net.set_dataset(ids, ids, segments=ids, minibatch=rows)
        assert telemetry.counter("trainer.dataset_relayouts").value == 0
    finally:
        root.common.telemetry.enabled = was
    assert net._data_d.format.layout == net._data_format.layout
