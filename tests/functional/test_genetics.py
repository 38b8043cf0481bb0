"""Genetics tier: Range + fix_config + the GA
driver evolving a Wine MLP hyperparameter across generations
(reference SURVEY.md §3.5, samples/MNIST/mnist_config.py:62)."""

import numpy
import pytest

from znicz_tpu.core.config import Config
from znicz_tpu.core.genetics import (
    Range, fix_config, enumerate_ranges, GeneticsOptimizer)
from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice


def _cfg():
    cfg = Config("test")
    cfg.update({
        "learning_rate": Range(0.002, 0.001, 0.5),
        "layers": [{"type": "all2all_tanh",
                    "->": {"output_sample_shape": Range(8, 4, 16)}}],
        "plain": 42,
    })
    return cfg


def test_range_validation_and_sampling():
    rng = Range(0.03, 0.0001, 0.9)
    assert rng.clip(5.0) == 0.9
    assert not rng.is_integer
    assert Range(100, 10, 500).is_integer
    assert Range(100, 10, 500).clip(77.6) == 78
    try:
        Range(2.0, 0.0, 1.0)
    except ValueError:
        pass
    else:
        raise AssertionError("out-of-bounds default accepted")


def test_fix_config_collapses_ranges():
    cfg = _cfg()
    assert len(enumerate_ranges(cfg)) == 2
    fix_config(cfg)
    assert cfg.learning_rate == 0.002
    assert cfg.layers[0]["->"]["output_sample_shape"] == 8
    assert cfg.plain == 42
    assert not enumerate_ranges(cfg)


def test_ga_improves_wine_fitness():
    """The GA must beat the (deliberately bad) default learning rate on
    Wine within a few cheap generations."""
    from znicz_tpu.samples.wine import WineWorkflow
    from znicz_tpu.core.config import root

    cfg = Config("ga")
    cfg.update({"learning_rate": Range(0.002, 0.001, 0.8)})
    evaluations = []

    prev_lr = root.wine.learning_rate

    def evaluate(c):
        prng.get(1).seed(12)
        prng.get(2).seed(13)
        root.wine.learning_rate = float(c.learning_rate)
        wf = WineWorkflow()
        wf.decision.max_epochs = 6
        wf.initialize(device=NumpyDevice())
        wf.run()
        # fitness: negative train error at the epoch budget
        fitness = -wf.decision.epoch_n_err[2]
        evaluations.append((float(c.learning_rate), fitness))
        return fitness

    opt = GeneticsOptimizer(evaluate, cfg, population_size=5,
                            generations=3,
                            rand=numpy.random.RandomState(5))
    try:
        best_values, best_fitness = opt.run()
    finally:
        root.wine.learning_rate = prev_lr

    assert len(opt.history) == 3
    default_fitness = evaluations[0][1]  # defaults evaluated first
    assert best_fitness > default_fitness, \
        "GA should beat lr=0.002 (default %s, best %s at lr=%s)" % (
            default_fitness, best_fitness, best_values)
    # generation-over-generation mean should not collapse
    assert opt.history[-1][0] >= opt.history[0][0]
    # the config ends patched with the winner
    assert cfg.learning_rate == best_values[0]


@pytest.mark.slow
def test_population_ga_parallel_evaluation_speedup():
    """The GA population evaluates CONCURRENTLY
    (one vmapped XLA computation per generation on the fused path) with
    wall-clock below the serial unit-graph evaluations at equal-or-better
    fitness."""
    import time
    from znicz_tpu.samples import wine
    from znicz_tpu.samples.wine import WineWorkflow
    from znicz_tpu.core.config import root

    epochs = 6
    prev_lr = root.wine.learning_rate

    def serial_evaluate(c):
        prng.get(1).seed(12)
        prng.get(2).seed(13)
        root.wine.learning_rate = float(c.learning_rate)
        wf = WineWorkflow()
        wf.decision.max_epochs = epochs
        wf.initialize(device=NumpyDevice())
        wf.run()
        # -err% — the scale the fused population evaluator reports
        return -wf.decision.epoch_n_err_pt[2]

    def make_cfg():
        cfg = Config("ga")
        cfg.update({"learning_rate": Range(0.002, 0.001, 0.8)})
        return cfg

    try:
        serial = GeneticsOptimizer(
            serial_evaluate, make_cfg(), population_size=6, generations=3,
            rand=numpy.random.RandomState(5))
        _, serial_best = serial.run()

        pop_eval = wine.population_evaluator(
            [(None, "learning_rate", None)], epochs=epochs)
        assert pop_eval is not None
        batch = GeneticsOptimizer(
            lambda c: (_ for _ in ()).throw(AssertionError(
                "serial evaluate must not be called")),
            make_cfg(), population_size=6, generations=3,
            rand=numpy.random.RandomState(5),
            evaluate_population=pop_eval)
        _, batch_best = batch.run()

        # steady-state wall-clock: one warm vmapped generation vs the
        # same individuals trained serially (compile amortizes across
        # generations/sessions; at real scale it is noise)
        gen = [[0.002 + 0.01 * i] for i in range(6)]
        # warm the SIZE-6 compiled variant (vmap specializes on the
        # population axis length)
        pop_eval([[0.5 + 0.01 * i] for i in range(6)])
        t0 = time.time()
        pop_eval(gen)
        batch_time = time.time() - t0
        t0 = time.time()
        for v in gen:
            cfg = make_cfg()
            cfg.learning_rate = v[0]
            serial_evaluate(cfg)
        serial_time = time.time() - t0
    finally:
        root.wine.learning_rate = prev_lr

    # fitness scales match (-train errors at the same epoch budget)
    assert batch_best >= serial_best - 2, (batch_best, serial_best)
    assert batch_time < serial_time, \
        "warm vmapped generation (%.3fs) should beat %d serial " \
        "workflow runs (%.3fs)" % (batch_time, len(gen), serial_time)


def test_population_evaluator_rejects_unknown_sites():
    """Sites that are not fused hyper slots fall back to the serial GA
    path (e.g. a loader knob)."""
    from znicz_tpu.samples import wine
    assert wine.population_evaluator(
        [(None, "minibatch_size", None), (None, "learning_rate", None)]) \
        is None


@pytest.mark.slow
def test_population_ga_tunes_two_sites_concurrently():
    """The generic mapping tunes >= 2 DISTINCT Range
    sites (learning rate AND weights decay) in one vmapped generation,
    with wall-clock below serial evaluation at equal-or-better fitness."""
    import time
    from znicz_tpu.samples import wine
    from znicz_tpu.samples.wine import WineWorkflow
    from znicz_tpu.core.config import root

    epochs = 6
    prev_lr = root.wine.learning_rate
    prev_wd = root.wine.weights_decay

    def make_cfg():
        cfg = Config("ga2")
        cfg.update({"learning_rate": Range(0.002, 0.001, 0.8),
                    "weights_decay": Range(0.0, 0.0, 0.01)})
        return cfg

    def serial_evaluate(c):
        prng.get(1).seed(12)
        prng.get(2).seed(13)
        root.wine.learning_rate = float(c.learning_rate)
        root.wine.weights_decay = float(c.weights_decay)
        wf = WineWorkflow()
        wf.decision.max_epochs = epochs
        wf.initialize(device=NumpyDevice())
        wf.run()
        return -wf.decision.epoch_n_err_pt[2]

    try:
        pop_eval = wine.population_evaluator(
            [(None, "learning_rate", None), (None, "weights_decay", None)],
            epochs=epochs)
        assert pop_eval is not None
        batch = GeneticsOptimizer(
            lambda c: (_ for _ in ()).throw(AssertionError(
                "serial evaluate must not be called")),
            make_cfg(), population_size=6, generations=3,
            rand=numpy.random.RandomState(5),
            evaluate_population=pop_eval)
        best_values, batch_best = batch.run()
        assert len(best_values) == 2

        serial = GeneticsOptimizer(
            serial_evaluate, make_cfg(), population_size=6, generations=3,
            rand=numpy.random.RandomState(5))
        _, serial_best = serial.run()

        gen = [[0.002 + 0.01 * i, 0.001 * i] for i in range(6)]
        pop_eval([[0.5 + 0.01 * i, 0.001] for i in range(6)])  # warm
        t0 = time.time()
        pop_eval(gen)
        batch_time = time.time() - t0
        t0 = time.time()
        for v in gen:
            cfg = make_cfg()
            cfg.learning_rate, cfg.weights_decay = v
            serial_evaluate(cfg)
        serial_time = time.time() - t0
    finally:
        root.wine.learning_rate = prev_lr
        root.wine.weights_decay = prev_wd

    assert batch_best >= serial_best - 2, (batch_best, serial_best)
    assert batch_time < serial_time, (batch_time, serial_time)


def test_config_values_to_hypers_per_layer_and_global():
    """Per-layer sites hit only their layer; global sites hit every
    parameterized layer; explicit *_bias keys decouple the bias slot."""
    from znicz_tpu.parallel import fused
    from znicz_tpu.parallel.population import config_values_to_hypers

    layers = [
        {"type": "all2all_tanh",
         "->": {"output_sample_shape": 6},
         "<-": {"learning_rate": 0.1, "learning_rate_bias": 0.2}},
        {"type": "softmax", "->": {"output_sample_shape": 3},
         "<-": {"learning_rate": 0.3}},
    ]
    specs = tuple(fused.build_specs(layers, 4, None))
    sites = [
        (layers[0]["<-"], "learning_rate", None),   # layer 0 only
        (None, "weights_decay", None),              # global
    ]
    mapper = config_values_to_hypers(sites, layers, specs)
    assert mapper is not None
    hypers = mapper([0.7, 0.005], specs)
    assert hypers[0]["w"]["lr"] == 0.7
    # explicit learning_rate_bias on layer 0 -> bias lr NOT coupled
    assert hypers[0]["b"]["lr"] == 0.2
    # layer 1 untouched by the per-layer site
    assert hypers[1]["w"]["lr"] == 0.3
    # global wd hits every layer's WEIGHTS slot; bias wd stays at its
    # parser default of 0 (fused._parse_hyper: weights_decay_bias
    # defaults to 0.0, not the weights value)
    assert hypers[0]["w"]["wd"] == 0.005
    assert hypers[1]["w"]["wd"] == 0.005
    assert hypers[1]["b"]["wd"] == 0.0
    # unmappable site -> None
    assert config_values_to_hypers(
        [(None, "minibatch_size", None)], layers, specs) is None