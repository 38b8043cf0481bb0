"""Multi-host (DCN) distributed training.

TPU-era replacement for the reference's master-slave socket transport
(SURVEY.md §5.8, veles launcher + nn_units.py:178-211 broadcast/
aggregate): every host runs the SAME SPMD program; the mesh spans all
hosts' devices; XLA routes per-layer collectives over ICI within a host
and only the gradient reduction over DCN.

Recipe::

    from znicz_tpu.parallel import multihost
    multihost.initialize()                 # no-op when single-process
    mesh = multihost.make_hybrid_mesh(model_parallel=2)
    net = FusedNet(layers, shape, mesh=mesh)
    for local_x, local_l in my_hosts_shard_of_the_data:
        x, l = multihost.global_batch(mesh, local_x, local_l)
        net.step(x, l)

Elasticity: the reference's master keeps training while slaves join and
leave; the SPMD equivalent is gang-scheduled, so host failure is handled
by checkpoint-restart instead — snapshots (core/snapshotter.py) carry
the full training state and the launcher's ``--snapshot`` resumes it.
"""

import os


import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_initialized = False


def initialize(coordinator_address=None, num_processes=None,
               process_id=None, **kwargs):
    """Bring up the JAX distributed runtime across hosts.

    A no-op for single-process runs (the common case and every test),
    and IDEMPOTENT: a second call in an already-distributed process
    returns True without touching the runtime (jax.distributed raises
    on double-initialize, and e.g. a serial GA constructs one Launcher
    per evaluation).  Arguments default from the standard env vars
    (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES, JAX_PROCESS_ID) —
    under TPU pod runtimes jax.distributed autodetects and none are
    needed.
    """
    global _initialized
    if _initialized:
        return True
    is_init = getattr(jax.distributed, "is_initialized", None)
    if is_init is not None and is_init():
        return True
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "0")) \
            or None
    if process_id is None:
        pid = os.environ.get("JAX_PROCESS_ID")
        process_id = int(pid) if pid is not None else None
    def _cpu_collectives():
        # multi-process CPU (tests / dev boxes) needs a cross-process
        # collectives implementation; gloo is the one shipped with jax.
        # Harmless if the backend turns out to be TPU (config is only
        # read by the CPU client).
        if "cpu" in (os.environ.get("JAX_PLATFORMS") or "cpu"):
            try:
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo")
            except Exception:
                pass

    if coordinator_address is None and num_processes in (None, 1):
        # no explicit config: managed cluster runtimes (TPU pods, GKE,
        # Slurm/MPI) carry their own env markers and jax.distributed
        # autodetects from them — skipping initialize there would let
        # every host train independently with NO gradient sync
        if _cluster_env_detected():
            _cpu_collectives()
            jax.distributed.initialize(**kwargs)
            _initialized = True
            return True
        return False  # genuinely single process
    _cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id, **kwargs)
    _initialized = True
    return True


#: env markers of the cluster runtimes jax.distributed can autodetect
_CLUSTER_ENV_VARS = (
    "MEGASCALE_COORDINATOR_ADDRESS",   # multislice
    "COORDINATOR_ADDRESS",
    "SLURM_JOB_ID",                    # Slurm
    "JOB_COMPLETION_INDEX",            # GKE indexed jobs
)


def _cluster_env_detected():
    if any(os.environ.get(v) for v in _CLUSTER_ENV_VARS):
        return True
    # TPU pod slice: only a MULTI-worker hostname list means multi-host
    # (single-host setups set one name)
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h.strip()]) > 1:
        return True
    try:
        if int(os.environ.get("OMPI_COMM_WORLD_SIZE", "1")) > 1:
            return True
    except ValueError:
        pass
    return False


def make_hybrid_mesh(model_parallel=1, devices=None):
    """(data, model) mesh over ALL processes' devices, laid out so that
    the model axis (all-gather heavy) stays inside one host's ICI domain
    and only the data-axis gradient psum crosses DCN.

    Single-process: equivalent to :func:`make_mesh` over the local
    devices.  Multi-process: uses mesh_utils.create_hybrid_device_mesh,
    which groups devices by process and orders DCN as the outermost
    axis.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % model_parallel:
        raise ValueError("%d devices not divisible by model_parallel %d"
                         % (n, model_parallel))
    n_processes = len({d.process_index for d in devices})
    if n_processes > 1:
        from jax.experimental import mesh_utils
        per_host = n // n_processes
        # TPU multislice: the DCN boundary is the SLICE (hosts inside a
        # slice are ICI-connected even across processes) — group by
        # slice with one DCN granule per slice.  Everything else
        # (multi-host single slice, CPU/GPU clusters, the 2-process CPU
        # elastic test) groups by process.
        n_slices = len({getattr(d, "slice_index", 0) or 0
                        for d in devices})
        if n_slices > 1:
            per_granule, n_granules, by_process = n // n_slices, \
                n_slices, False
        else:
            per_granule, n_granules, by_process = per_host, \
                n_processes, True
        if per_granule % model_parallel:
            raise ValueError(
                "model_parallel %d does not fit inside one DCN "
                "granule's %d devices — the model axis must not cross "
                "DCN" % (model_parallel, per_granule))
        arr = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(per_granule // model_parallel, model_parallel),
            dcn_mesh_shape=(n_granules, 1), devices=devices,
            process_is_granule=by_process)
        return Mesh(arr, ("data", "model"))
    from znicz_tpu.parallel.mesh import make_mesh
    return make_mesh(model_parallel=model_parallel, devices=devices)


def global_batch(mesh, local_x, local_labels):
    """Assemble per-process host shards into GLOBAL device arrays
    sharded over the mesh's data axis.

    Each process passes only ITS slice of the global batch (global batch
    size = sum of local batch sizes).  Single-process this is just a
    sharded device_put.
    """
    xs = NamedSharding(mesh, P("data", *([None] * (local_x.ndim - 1))))
    ls = NamedSharding(mesh, P("data"))
    if jax.process_count() == 1:
        return jax.device_put(local_x, xs), jax.device_put(local_labels, ls)
    x = jax.make_array_from_process_local_data(xs, local_x)
    labels = jax.make_array_from_process_local_data(ls, local_labels)
    return x, labels


# -- telemetry aggregation ---------------------------------------------------

def _flatten_telemetry(snap):
    """Deterministic (kind, name) -> float flattening of the numeric
    parts of a telemetry snapshot.  SPMD gangs run the same program, so
    every host produces the same key list — verified by the caller."""
    items = []
    for kind in ("counters", "gauges"):
        for k in sorted(snap.get(kind, {})):
            items.append((kind, k, float(snap[kind][k])))
    for k in sorted(snap.get("histograms", {})):
        h = snap["histograms"][k]
        items.append(("hist_count", k, float(h.get("count", 0))))
        items.append(("hist_sum", k, float(h.get("sum", 0.0))))
    return items


def merge_telemetry_snapshots(snaps):
    """Merge per-host telemetry snapshots into one view: counters and
    histogram count/sum are SUMMED, gauges take the MAX (a summed
    "loader.epoch" gauge would be nonsense).  Histogram percentiles
    are kept from the FIRST snapshot (this host) and flagged — exact
    cross-host percentile merge would need the raw reservoirs over
    DCN, which the counters' one-allgather budget doesn't buy."""
    if not snaps:
        return {}
    merged = {"counters": {}, "gauges": {}, "histograms": {}}
    for kind, agg in (("counters", sum), ("gauges", max)):
        keys = set()
        for s in snaps:
            keys.update(s.get(kind, {}))
        for k in sorted(keys):
            vals = [s.get(kind, {}).get(k, 0) for s in snaps]
            v = agg(vals)
            merged[kind][k] = int(v) if kind == "counters" else v
    hkeys = set()
    for s in snaps:
        hkeys.update(s.get("histograms", {}))
    for k in sorted(hkeys):
        hs = [s.get("histograms", {}).get(k) or {} for s in snaps]
        h = dict(hs[0])
        h["count"] = int(sum(x.get("count", 0) for x in hs))
        h["sum"] = float(sum(x.get("sum", 0.0) for x in hs))
        if any(x.get("count") for x in hs[1:]):
            h["percentiles_local_host_only"] = True
        merged["histograms"][k] = h
    merged["hosts"] = len(snaps)
    return merged


def aggregate_telemetry(snap):
    """Reduce every host's numeric telemetry into ONE merged view with
    a single allgather (collective — every process of the gang must
    call it, e.g. via ``telemetry.merged_snapshot()``).  Single-process
    it is the identity.  If the hosts' key sets disagree (a
    non-SPMD-identical code path registered an extra series), the
    local snapshot is returned unreduced rather than mis-summing
    misaligned columns."""
    import numpy
    import zlib

    if jax.process_count() == 1:
        return snap
    from jax.experimental import multihost_utils
    items = _flatten_telemetry(snap)
    keys_sig = zlib.crc32("|".join(
        "%s:%s" % (kind, k) for kind, k, _ in items).encode())
    # two collectives, BOTH shape-consistent across hosts: the first is
    # a fixed-shape (2,) signature exchange — hosts whose registries
    # diverged (a rank-0-only series like snapshotter.exports) would
    # otherwise feed different-length vectors into ONE allgather, which
    # crashes or hangs the collective before any guard can run.  Every
    # host sees every signature, so every host takes the same branch.
    sig = numpy.array([float(len(items)), float(keys_sig)],
                      dtype=numpy.float64)
    sigs = numpy.asarray(multihost_utils.process_allgather(sig))
    if not (sigs[:, 0] == len(items)).all() or \
            not (sigs[:, 1] == float(keys_sig)).all():
        snap = dict(snap)
        snap["aggregated"] = False
        return snap
    # signatures agree -> identical keys -> identical vector length
    vec = numpy.array([v for _, _, v in items], dtype=numpy.float64)
    gathered = numpy.asarray(
        multihost_utils.process_allgather(vec))  # (nproc, n)
    # rebuild per-host snapshots from the gathered columns, merge
    snaps = []
    for row in gathered:
        s = {"counters": {}, "gauges": {}, "histograms": {}}
        for (kind, k, _), v in zip(items, row):
            if kind in ("counters", "gauges"):
                s[kind][k] = v
            elif kind == "hist_count":
                s["histograms"].setdefault(k, {})["count"] = v
            else:
                s["histograms"].setdefault(k, {})["sum"] = v
        snaps.append(s)
    # carry this host's percentiles into slot 0 so the merge keeps them
    for k, h in snap.get("histograms", {}).items():
        snaps[jax.process_index()]["histograms"][k] = dict(
            h, **snaps[jax.process_index()]["histograms"].get(k, {}))
    local = snaps.pop(jax.process_index())
    merged = merge_telemetry_snapshots([local] + snaps)
    merged["hosts"] = int(jax.process_count())
    if "trace" in snap:
        merged["trace"] = snap["trace"]
    return merged


def host_shard(global_size, process_index=None, process_count=None):
    """(start, stop) of this host's contiguous slice of a global batch
    or dataset — the per-host data-loading contract."""
    process_index = jax.process_index() if process_index is None \
        else process_index
    process_count = jax.process_count() if process_count is None \
        else process_count
    if global_size % process_count:
        raise ValueError("global size %d not divisible by %d processes"
                         % (global_size, process_count))
    per = global_size // process_count
    return process_index * per, (process_index + 1) * per
