"""Population-parallel GA evaluation — vmap the fused trainer.

The reference's genetic optimizer sprayed workflow evaluations across a
master–slave cluster (SURVEY.md §3.5).  The TPU-native equivalent
batches them: every individual of a GA generation trains CONCURRENTLY as
one vmapped XLA computation over the fused train step — the population
axis becomes a batch axis of the compiled program, so N individuals cost
roughly one individual's wall-clock on an undersubscribed chip.

All individuals share one weight init (drawn once from the seeded PRNG,
same draw order as the unit path) and a FIXED minibatch order — the GA
compares hyperparameters, so the data stream must be identical across
individuals anyway.
"""

import numpy

import jax
import jax.numpy as jnp

from znicz_tpu.core import prng
from znicz_tpu.parallel import fused


def make_population_evaluator(layers, input_sample_shape,
                              train_x, train_y, val_x, val_y,
                              values_to_hypers, epochs=6,
                              minibatch_size=None, rand=None,
                              dtype=numpy.float32, defaults=None):
    """Build ``evaluate_population(value_vectors) -> [fitness]`` for
    :class:`znicz_tpu.core.genetics.GeneticsOptimizer`.

    ``values_to_hypers(values, specs)`` maps one GA value vector onto a
    fused hyper pytree (see :func:`znicz_tpu.parallel.fused
    .default_hypers`); fitness is the negative validation error PERCENT
    after ``epochs`` of training (softmax objective) — the same scale
    the serial ``--optimize`` fallback reports (-best_n_err_pt).
    """
    specs = tuple(fused.build_specs(layers, input_sample_shape, defaults))
    if not specs[-1].is_softmax:
        raise ValueError("population evaluator scores a softmax head")
    params0 = fused.init_params(specs, rand or prng.get(), dtype)
    state0 = fused.init_opt_state(specs, params0)
    train_x = numpy.asarray(train_x, dtype)
    train_y = numpy.asarray(train_y, numpy.int32)
    n = len(train_x)
    # one fixed shuffle: datasets often arrive class-ordered (UCI Wine),
    # and class-homogeneous minibatches cripple SGD; a deterministic
    # permutation keeps the stream identical across individuals
    perm = numpy.random.RandomState(0x5EED).permutation(n)
    train_x, train_y = train_x[perm], train_y[perm]
    mb = minibatch_size or n
    steps = max(1, n // mb)
    xs = jnp.asarray(train_x[:steps * mb].reshape((steps, mb) +
                                                  train_x.shape[1:]))
    ys = jnp.asarray(train_y[:steps * mb].reshape(steps, mb))
    vx = jnp.asarray(numpy.asarray(val_x, dtype))
    vy = jnp.asarray(numpy.asarray(val_y, numpy.int32))
    p0 = jax.tree.map(jnp.asarray, params0)
    s0 = jax.tree.map(jnp.asarray, state0)

    def train_eval(hypers):
        def epoch(carry, _):
            def step(carry, batch):
                p, s = carry
                x, y = batch
                p, s, m = fused._train_step(p, s, x, y, specs,
                                            hypers=hypers)
                return (p, s), m["loss"]
            carry, losses = jax.lax.scan(step, carry, (xs, ys))
            return carry, losses[-1]

        (p, _), _ = jax.lax.scan(epoch, (p0, s0), None, length=epochs)
        probs = fused.forward(p, vx, specs)
        n_err = (jnp.argmax(probs, axis=1) != vy).sum()
        return -100.0 * n_err.astype(jnp.float32) / vy.shape[0]

    fn = jax.jit(jax.vmap(train_eval))

    def evaluate_population(value_vectors):
        hypers = [values_to_hypers(list(v), specs) for v in value_vectors]
        stacked = jax.tree.map(lambda *leaves: jnp.stack(
            [jnp.asarray(l, jnp.float32) for l in leaves]), *hypers)
        return [float(f) for f in numpy.asarray(fn(stacked))]

    return evaluate_population


def uniform_lr_hypers(values, specs):
    """The common single-site mapping: one GA value = the learning rate
    of every parameterized layer (weights and bias)."""
    lr = float(values[0])
    hypers = []
    for spec in specs:
        if spec.kind in ("fc", "conv"):
            h = {"w": dict(spec.hyper, lr=lr)}
            if spec.include_bias:
                h["b"] = dict(spec.hyper_bias, lr=lr)
            hypers.append(h)
        else:
            hypers.append({})
    return hypers


#: backward-kwargs key -> (gd_math hyper field, is_bias_slot,
#: couples_to_bias) — coupling mirrors the config parser exactly
#: (fused._parse_hyper: bias lr/moment/l1_vs_l2 default to the weights
#: value, bias wd defaults to 0, ortho never applies to bias;
#: reference "<-" contract, standard_workflow_base.py:406-422)
HYPER_KEYS = {
    "learning_rate": ("lr", False, True),
    "learning_rate_bias": ("lr", True, False),
    "weights_decay": ("wd", False, False),
    "weights_decay_bias": ("wd", True, False),
    "gradient_moment": ("moment", False, True),
    "gradient_moment_bias": ("moment", True, False),
    "l1_vs_l2": ("l1_vs_l2", False, True),
    "l1_vs_l2_bias": ("l1_vs_l2", True, False),
    "factor_ortho": ("factor_ortho", False, False),
}


def config_values_to_hypers(sites, layers, specs):
    """Build ``values_to_hypers`` automatically from the Range-tagged
    sites of a sample's config (the reference GA
    tunes arbitrary ``Range`` config scalars, SURVEY.md §3.5).

    Each site maps onto fused hyper slots:

    * a Range inside a specific layer's dict (or its "<-" sub-dict)
      tunes THAT layer's slot;
    * a Range anywhere else with a known hyper key (``learning_rate``,
      ``weights_decay``, ``gradient_moment``, ...) tunes the slot on
      EVERY parameterized layer — the common global-hyper pattern
      (reference mnist_config.py:62);
    * the weights slot also drives the bias slot when the layer declares
      no explicit ``<key>_bias`` — the same coupling the config parser
      applies (fused._parse_hyper).

    Returns ``values_to_hypers(values, specs) -> hyper pytree`` or
    ``None`` when any site cannot be mapped (the serial GA path remains
    the general fallback)."""
    param_idx = [i for i, s in enumerate(specs)
                 if s.kind in ("fc", "conv")]
    plans = []  # per site: [(spec index, field, bias?, couple_bias)...]
    for container, key, _rng in sites:
        if key not in HYPER_KEYS:
            return None
        field, bias, couples = HYPER_KEYS[key]

        def _couple(i):
            # parser parity: the bias slot follows the weights value
            # only for coupling keys AND only when the layer declares
            # no explicit <key>_bias override
            sub = (layers[i].get("<-") or {}) \
                if isinstance(layers[i], dict) else {}
            return couples and (key + "_bias") not in sub

        targets = None
        for i, layer in enumerate(layers):
            sub = layer.get("<-") if isinstance(layer, dict) else None
            if container is sub or container is layer:
                if i not in param_idx:
                    return None
                targets = [(i, field, bias, _couple(i))]
                break
        if targets is None:
            # global site: every parameterized layer
            targets = [(i, field, bias, _couple(i)) for i in param_idx]
        plans.append(targets)

    def values_to_hypers(values, specs):
        hypers = []
        for spec in specs:
            if spec.kind in ("fc", "conv"):
                h = {"w": dict(spec.hyper)}
                if spec.include_bias:
                    h["b"] = dict(spec.hyper_bias)
                hypers.append(h)
            else:
                hypers.append({})
        for value, targets in zip(values, plans):
            value = float(value)
            for i, field, bias, couple_bias in targets:
                if bias:
                    if "b" in hypers[i]:
                        hypers[i]["b"][field] = value
                else:
                    hypers[i]["w"][field] = value
                    if couple_bias and "b" in hypers[i]:
                        hypers[i]["b"][field] = value
        return hypers

    return values_to_hypers


def _collapse_ranges(obj):
    """Deep-copy a layers config with Range values collapsed to their
    defaults (the evaluator's baseline; the GA overrides via the mapped
    hyper slots, not by mutating the config)."""
    from znicz_tpu.core.genetics import Range
    if isinstance(obj, Range):
        return obj.default
    if isinstance(obj, dict):
        return {k: _collapse_ranges(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_collapse_ranges(v) for v in obj)
    return obj


def workflow_population_evaluator(ns, sites, epochs=None, seed=12,
                                  loader_kwargs=None, verbose=False):
    """Generic ``--optimize`` fused path for StandardWorkflow samples:
    builds the sample's registered loader from its config namespace
    ``ns`` (root.<sample>), maps the Range ``sites`` onto fused hyper
    slots, and returns the vmapped population evaluator — or ``None``
    when the topology/sites are not fusable (serial fallback; with
    ``verbose`` the reason is printed so the fallback is visible)."""
    from znicz_tpu.core.workflow import DummyWorkflow
    from znicz_tpu.loader.base import UserLoaderRegistry, VALID, TRAIN

    def bail(reason):
        if verbose:
            import logging
            from znicz_tpu.core.logger import setup_logging
            setup_logging()
            logging.getLogger("genetics").info(
                "fused GA unavailable: %s; evaluating serially", reason)
        return None

    layers = _collapse_ranges(list(ns.layers))
    loader_cfg = dict(ns.loader.as_dict() if hasattr(ns.loader, "as_dict")
                      else ns.loader)
    loader_cfg.update(loader_kwargs or {})
    try:
        loader_cls = UserLoaderRegistry.get_factory(ns.loader_name)
        loader = loader_cls(DummyWorkflow(), **loader_cfg)
        loader.initialize()
    except Exception as e:
        return bail("loader %r failed to initialize (%s)"
                    % (ns.loader_name, e))
    data = getattr(loader, "original_data", None)
    labels = getattr(loader, "original_labels", None)
    if data is None or not data or not labels:
        return bail("loader exposes no in-memory dataset/labels")
    x = numpy.asarray(data.mem)
    y = numpy.asarray(labels, dtype=numpy.int32)
    vs, ve = loader.class_index_range(VALID)
    ts, te = loader.class_index_range(TRAIN)
    if te <= ts:
        return bail("loader has no TRAIN segment")
    if ve <= vs:  # no validation split: score on train
        vs, ve = ts, te
    sample_shape = tuple(x.shape[1:])
    last = layers[-1] if layers else {}
    if isinstance(last, dict) and last.get("type") == "softmax":
        # head width comes from the loader at link time when the config
        # omits it (StandardWorkflowBase link_forwards parity)
        fwd = last.setdefault("->", {})
        if "output_sample_shape" not in fwd and \
                "output_samples" not in fwd:
            try:
                fwd["output_sample_shape"] = int(
                    loader.unique_labels_count)
            except Exception:
                pass
    try:
        specs = tuple(fused.build_specs(layers, sample_shape, None))
    except Exception as e:
        return bail("topology is not fusable (%s)" % e)
    if not specs[-1].is_softmax:
        return bail("population fitness needs a softmax head")
    # site identity must match the ORIGINAL config dicts (the collapsed
    # copy exists only for spec building)
    mapper = config_values_to_hypers(sites, list(ns.layers), specs)
    if mapper is None:
        return bail("a Range site does not map onto fused hyper slots")
    max_epochs = getattr(ns.decision, "max_epochs", None)
    return make_population_evaluator(
        layers, sample_shape, x[ts:te], y[ts:te], x[vs:ve], y[vs:ve],
        mapper, epochs=epochs or min(int(max_epochs or 10), 10),
        minibatch_size=int(loader_cfg.get("minibatch_size") or 0) or None,
        rand=prng.RandomGenerator().seed(seed))
