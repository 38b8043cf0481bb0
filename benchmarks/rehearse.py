"""Rehearse the cells without the chip.  Never prints a rate.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py tiny   [cell ...]
    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py compile [cell ...]

``tiny`` runs a cell end to end on the CPU at a tiny size (four virtual
devices stand in for a four-chip cell: set
``XLA_FLAGS=--xla_force_host_platform_device_count=4``): build, warm,
a short window, capture, reference, comparison.  It prints the numbers
compared and no result line.

``compile`` builds each cell's train-window and indexed validation programs
at the real size and compiles them for a *described* v5e (one device, or a
2x2 mesh with ``NamedSharding``), then prints ``memory_analysis()`` and the
resident arrays against the 4 GB floor.  It describes the arguments of
``run_window_indexed`` over labelled rows; a family with another entry
brings a rehearsal of its own.  A compile that passes is not a chip run.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmarks import run as run_mod   # noqa: E402

FLOOR = 4e9


def tiny_mix(mix):
    """The job mix at the tiny size its own file gives for rehearsals."""
    return dict(mix, **mix["tiny"])


def log(msg):
    print("[rehearse] %s" % msg, file=sys.stderr, flush=True)


def tiny_cell(cell, cfg, mix, limits, sabotage=None, seed=2147483659,
              seconds=0.5):
    """One cell from its parts, at its mix's tiny size: the job, the
    family's reference and comparison.  Returns (correct, numbers)."""
    from benchmarks import families
    from benchmarks.lib import compare, job
    name = cell["name"]
    mix = tiny_mix(mix)
    fam = families.load(cfg)
    run = job.run_cell(cell, cfg, mix, seed, seconds, False, ROOT,
                       time.perf_counter(), log, sabotage=sabotage)
    refout = fam.follow(cfg, mix, run, chips=int(cell["chips"]))
    nums, where = fam.numbers(run, refout, cfg, limits, fam.plan(cfg, mix))
    for n, v, lim in nums:
        print("%s compared %-24s %.6g  limit %.6g" % (name, n, v, lim))
    print("%s epochs=%d correct=%s %s"
          % (name, run["epochs"], compare.decide(nums), where))
    return compare.decide(nums), nums


def tiny(names, **kwargs):
    """Returns {cell: (correct, numbers)}."""
    return {name: tiny_cell(*run_mod.resolve(name)[:4], **kwargs)
            for name in names}


def _described(tree, sharding):
    import jax
    import numpy
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(numpy.shape(a), numpy.asarray(a).dtype
                                       if not hasattr(a, "dtype") else a.dtype,
                                       sharding=sharding), tree)


def compile_cells(names):
    import importlib
    import numpy
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    from benchmarks.lib import job
    from znicz_tpu.parallel import fused

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        cell, cfg, mix, _, _ = run_mod.resolve(name)
        chips = int(cell["chips"])
        module = importlib.import_module(cfg["sample"])
        layers = job.program_layers(module, cfg)
        shape = tuple(cfg["input_sample_shape"])
        batch, k = int(mix["minibatch"]), int(mix["window"])
        n_rows = int(mix["n_train"]) + int(mix["n_valid"])
        if chips > 1:
            mesh = Mesh(numpy.array(topo.devices[:chips]).reshape(chips, 1),
                        ("data", "model"))
            rep = NamedSharding(mesh, P())
        else:
            mesh, rep = None, SingleDeviceSharding(topo.devices[0])
        # the net places its arrays as it is built; a described device
        # holds none, so placement is skipped and shapes are lowered
        real_put = jax.device_put
        jax.device_put = lambda x, *a, **kw: x
        try:
            net = fused.FusedNet(layers, shape, mesh=mesh,
                                 compute_dtype=jnp.bfloat16)
        finally:
            jax.device_put = real_put
        # the trainer's hyper feed carries every proxy field
        hy = jax.tree.map(lambda v: numpy.full((k,), v, numpy.float32),
                          fused.default_hypers(net.specs))
        acc = net.window_acc_zeros()
        if chips > 1:
            def shard(v):
                return NamedSharding(mesh, P("data", *([None] * (
                    numpy.ndim(v) - 1))))
            acc_d = {kk: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                              sharding=shard(v))
                     for kk, v in acc.items()}
            idx_sh = NamedSharding(mesh, P(None, "data"))
        else:
            acc_d = _described(acc, rep)
            idx_sh = rep
        data = jax.ShapeDtypeStruct((n_rows,) + shape, jnp.bfloat16,
                                    sharding=rep)
        lbl = jax.ShapeDtypeStruct((n_rows,), jnp.int32, sharding=rep)
        idx = jax.ShapeDtypeStruct((k, batch), jnp.int32, sharding=idx_sh)
        bs = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=rep)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        resident = (n_rows * int(numpy.prod(shape)) * 2
                    + sum(v.nbytes for p in net.params for v in p.values())
                    * 2)
        print("%s: resident on each device (bf16 data set, f32 parameters "
              "and momentum): %.2f GB" % (name, resident / 1e9))
        for final in ((False, True) if chips > 1 else (False,)):
            fn = net._get_window_fn(k, "indexed", final=final)
            t0 = time.perf_counter()
            compiled = fn.lower(
                _described(net.params, rep), _described(net.state, rep), key,
                data, lbl, idx, None, bs, _described(hy, rep),
                acc_d).compile()
            mem = compiled.memory_analysis()
            total = resident + mem.temp_size_in_bytes
            text = compiled.as_text()
            print("%s: train window k=%d final=%s compiled in %.0f s: "
                  "temp %.2f GB, arguments %.2f GB, output %.2f GB; resident"
                  " + temp %.2f GB (%s the %.0f GB floor); all-reduce ops %d"
                  % (name, k, final, time.perf_counter() - t0,
                     mem.temp_size_in_bytes / 1e9,
                     mem.argument_size_in_bytes / 1e9,
                     mem.output_size_in_bytes / 1e9, total / 1e9,
                     "over" if total >= FLOOR else "UNDER", FLOOR / 1e9,
                     text.count(" all-reduce(") + text.count(
                         " all-reduce-start(")))
        # the validation pass the cells run: the minibatch's row indices
        # cross, the rows are gathered from the resident data set
        v_idx = jax.ShapeDtypeStruct(
            (batch,), jnp.int32,
            sharding=rep if chips == 1 else NamedSharding(mesh, P("data")))
        t0 = time.perf_counter()
        compiled = net._fwd_idx_at.lower(_described(net.params, rep), data,
                                         v_idx, None).compile()
        mem = compiled.memory_analysis()
        print("%s: indexed validation forward compiled in %.0f s: temp "
              "%.2f GB, arguments %.2f GB"
              % (name, time.perf_counter() - t0,
                 mem.temp_size_in_bytes / 1e9,
                 mem.argument_size_in_bytes / 1e9))


def main(argv):
    if len(argv) < 1 or argv[0] not in ("tiny", "compile"):
        raise SystemExit(__doc__)
    names = argv[1:] or [w["name"] for w in json.load(
        open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
    if argv[0] == "tiny":
        tiny(names)
    else:
        compile_cells(names)


if __name__ == "__main__":
    main(sys.argv[1:])
