"""A routed language model: a mixture of experts under window and global
attention.

The mechanism, not a model's name: an embedding, then layers of
pre-normed attention and a routed mixture of experts, each in a residual
entry, and a plain head (final norm, untied output product,
cross-entropy).  A layer's router reads the layer's input, before
attention; the ``moe`` entry that names it chooses ``top_k`` of all
``experts`` for every token, weighs them by the softmax over the chosen
logits and adds the part of the result that the experts held here give
(``held``: this chip's share of an expert-parallel group; all of them
where none is named).  Attention differs by layer: ``rope_layout`` says
which layers turn queries and keys by their position (the others see no
position at all), ``window_layout`` which attend a window of keys only.
Rows are packed documents, as in :mod:`looped_lm`.  It trains through the
fused trainer only.

The preset here is tiny, for the CPU; ``make_layers`` takes the widths of
a real one (``benchmarks/configs/`` holds a published configuration).
"""

from znicz_tpu.core.config import root
# registers the loader the preset names
from znicz_tpu.samples.research.looped_lm import (  # noqa: F401
    SyntheticTokenRows)
from znicz_tpu.standard_workflow import StandardWorkflow


def make_layers(vocab=64, dim=32, heads=4, kv_heads=2, head_dim=8,
                experts=8, top_k=2, held_first=0, held_count=8, hidden=16,
                n_layers=4, rope_layout=(0, 1, 1, 1),
                window_layout=(0, 1, 1, 1), window=8, rope_base=1.5e6,
                eps=1e-6, stddev=0.02, activation="relu", q_block=None,
                token_block=None, learning_rate=3e-4, weights_decay=0.1,
                adam_beta1=0.9, adam_beta2=0.95, adam_eps=1e-8):
    """The ``layers`` config of a routed language model; layer ``i`` takes
    ``rope_layout[i % len]`` and ``window_layout[i % len]``."""
    bwd = {"learning_rate": learning_rate, "weights_decay": weights_decay,
           "weights_decay_bias": 0.0, "solvers": ["adamw"],
           "adam_beta1": adam_beta1, "adam_beta2": adam_beta2,
           "adam_eps": adam_eps}

    def norm(name):
        return {"type": "rmsnorm", "name": name, "->": {"eps": eps},
                "<-": dict(bwd)}

    def block(i):
        windowed = bool(window_layout[i % len(window_layout)])
        return [
            {"type": "router", "name": "l%d_router" % i,
             "->": {"experts": experts, "weights_stddev": stddev},
             "<-": dict(bwd)},
            {"type": "residual", "remat": True, "layers": [
                norm("l%d_norm1" % i),
                {"type": "attention", "name": "l%d_attn" % i,
                 "->": {"heads": heads, "kv_heads": kv_heads,
                        "head_dim": head_dim,
                        "rope": bool(rope_layout[i % len(rope_layout)]),
                        "rope_base": rope_base,
                        "window": int(window) if windowed else None,
                        "q_block": q_block, "weights_stddev": stddev},
                 "<-": dict(bwd)}]},
            {"type": "residual", "remat": True, "layers": [
                norm("l%d_norm2" % i),
                {"type": "moe", "name": "l%d_moe" % i,
                 "->": {"router": "l%d_router" % i, "experts": experts,
                        "top_k": top_k, "held": [held_first, held_count],
                        "hidden": hidden, "activation": activation,
                        "weights_stddev": stddev},
                 "<-": dict(bwd)}]}]

    layers = [{"type": "embedding", "name": "embed",
               "->": {"vocab": vocab, "dim": dim, "weights_stddev": stddev},
               "<-": dict(bwd)}]
    for i in range(n_layers):
        layers.extend(block(i))
    layers.append({"type": "lm_head", "name": "head",
                   "->": {"vocab": vocab, "eps": eps,
                          "token_block": token_block,
                          "weights_stddev": stddev},
                   "<-": dict(bwd)})
    return layers


root.routed_lm.update({
    "decision": {"fail_iterations": 50, "max_epochs": 20},
    "snapshotter": {"prefix": "routed_lm", "interval": 1,
                    "time_interval": 0, "compression": ""},
    "loss_function": "tokens",
    "loader_name": "synthetic_token_rows",
    "loader": {"minibatch_size": 8, "vocab": 64, "seq_len": 32,
               "n_train": 64, "n_valid": 16},
    # the tiny preset (CPU): widths of a toy, the mechanism whole
    "model": {"vocab": 64, "dim": 32, "heads": 4, "kv_heads": 2,
              "head_dim": 8, "experts": 8, "top_k": 2, "held_count": 8,
              "hidden": 16, "n_layers": 4, "window": 8, "q_block": 16,
              "token_block": 64, "learning_rate": 3e-3},
})


class RoutedLMWorkflow(StandardWorkflow):
    """Loader -> fused trainer -> token evaluator -> decision."""


def build(layers=None, loader_config=None, decision_config=None, **kwargs):
    cfg = root.routed_lm
    loader_cfg = cfg.loader.as_dict()
    loader_cfg.update(loader_config or {})
    decision_cfg = cfg.decision.as_dict()
    decision_cfg.update(decision_config or {})
    kwargs.setdefault("loss_function", cfg.loss_function)
    if kwargs.get("fused") is None:
        # the one path this model has, on with or without ``--fused``
        kwargs["fused"] = {}
    snap_cfg = cfg.snapshotter.as_dict()
    snap_cfg.update(kwargs.pop("snapshotter_config", None) or {})
    return RoutedLMWorkflow(
        layers=layers if layers is not None
        else make_layers(**cfg.model.as_dict()),
        loader_name=cfg.loader_name, loader_config=loader_cfg,
        decision_config=decision_cfg,
        snapshotter_config=snap_cfg, **kwargs)


def run_sample(device=None, **kwargs):
    wf = build(**kwargs)
    wf.initialize(device=device)
    wf.run()
    return wf


def run(load, main):
    """Launcher contract."""
    load(build)
    main()
