"""A language model of shared and routed experts, balanced by a selection
bias, under gated, normed attention.

The mechanism, not a model's name: an embedding whose output is multiplied
by ``sqrt(dim)``, then layers of two residual entries each, and a plain
head (final norm, untied output product, cross-entropy).  Every residual
branch is normed on its way in AND on its way out (``x += RMS(f(RMS(x)))``).

The attention branch: a per-head norm with a learned gain on queries and
keys (``qk_norm``), rotary and a window of keys on the layers
``window_layout`` marks and no position encoding at all on the others,
which attend their whole document; the heads' output is multiplied by the
sigmoid of a fifth projection of the branch's input (``gate``).

The feed-forward branch: a gated MLP in the first ``dense_layers`` layers;
in the others a router over all ``experts`` whose scores are sigmoids.  A
token goes to the ``top_k`` largest of score plus selection bias; its
weights are the chosen scores alone, divided by their sum and times
``route_scale``.  The ``moe`` entry adds what the experts held here give
(``held``: this chip's share of an expert-parallel group) to a shared
expert that every token passes, inside the one branch, so the norm on the
way out is of their sum.  After every optimizer step the bias of every
expert moves by ``balance_rate`` toward the mean load (up where the step
sent it fewer tokens than the mean, down where more); it takes no gradient
(``parallel/fused.py:_apply_updates``, ``ops/transformer.py:balance``).
There is no auxiliary loss.

Rows are packed documents, as in :mod:`looped_lm`.  It trains through the
fused trainer only.  The preset here is tiny, for the CPU; ``make_layers``
takes the widths of a real one (``benchmarks/configs/`` holds a published
configuration).
"""

from znicz_tpu.core.config import root
# registers the loader the preset names
from znicz_tpu.samples.research.looped_lm import (  # noqa: F401
    SyntheticTokenRows)
from znicz_tpu.standard_workflow import StandardWorkflow


def make_layers(vocab=64, dim=32, heads=4, kv_heads=2, head_dim=8,
                dense_hidden=48, experts=8, top_k=2, held_first=0,
                held_count=8, hidden=16, shared_hidden=16, n_layers=5,
                dense_layers=1, window_layout=(1, 1, 0, 1, 1), window=8,
                rope_base=10000.0, eps=1e-5, stddev=0.02, route_scale=1.5,
                balance_rate=1e-3, q_block=None, token_block=None,
                learning_rate=3e-4, weights_decay=0.1, adam_beta1=0.9,
                adam_beta2=0.95, adam_eps=1e-8):
    """The ``layers`` config; layer ``i`` is windowed and rotary where
    ``window_layout[i % len]`` says so, else global with no position."""
    bwd = {"learning_rate": learning_rate, "weights_decay": weights_decay,
           "weights_decay_bias": 0.0, "solvers": ["adamw"],
           "adam_beta1": adam_beta1, "adam_beta2": adam_beta2,
           "adam_eps": adam_eps}

    def norm(name):
        return {"type": "rmsnorm", "name": name, "->": {"eps": eps},
                "<-": dict(bwd)}

    def block(i):
        windowed = bool(window_layout[i % len(window_layout)])
        if i < dense_layers:
            feed = [{"type": "gated_mlp", "name": "l%d_mlp" % i,
                     "->": {"hidden": dense_hidden,
                            "weights_stddev": stddev},
                     "<-": dict(bwd)}]
        else:
            feed = [
                {"type": "router", "name": "l%d_router" % i,
                 "->": {"experts": experts, "weights_stddev": stddev},
                 "<-": dict(bwd)},
                {"type": "moe", "name": "l%d_moe" % i,
                 "->": {"router": "l%d_router" % i, "experts": experts,
                        "top_k": top_k, "held": [held_first, held_count],
                        "hidden": hidden, "shared_hidden": shared_hidden,
                        "activation": "silu", "score": "sigmoid",
                        "route_scale": route_scale,
                        "balance_rate": balance_rate,
                        "weights_stddev": stddev},
                 "<-": dict(bwd)}]
        return [
            {"type": "residual", "remat": True, "layers": [
                norm("l%d_norm1" % i),
                {"type": "attention", "name": "l%d_attn" % i,
                 "->": {"heads": heads, "kv_heads": kv_heads,
                        "head_dim": head_dim, "rope": windowed,
                        "rope_base": rope_base,
                        "window": int(window) if windowed else None,
                        "qk_norm": True, "gate": True, "eps": eps,
                        "q_block": q_block, "weights_stddev": stddev},
                 "<-": dict(bwd)},
                norm("l%d_norm2" % i)]},
            {"type": "residual", "remat": True, "layers":
                [norm("l%d_norm3" % i)] + feed + [norm("l%d_norm4" % i)]}]

    layers = [{"type": "embedding", "name": "embed",
               "->": {"vocab": vocab, "dim": dim, "scale": float(dim) ** 0.5,
                      "weights_stddev": stddev},
               "<-": dict(bwd)}]
    for i in range(n_layers):
        layers.extend(block(i))
    layers.append({"type": "lm_head", "name": "head",
                   "->": {"vocab": vocab, "eps": eps,
                          "token_block": token_block,
                          "weights_stddev": stddev},
                   "<-": dict(bwd)})
    return layers


root.shared_moe_lm.update({
    "decision": {"fail_iterations": 50, "max_epochs": 20},
    "snapshotter": {"prefix": "shared_moe_lm", "interval": 1,
                    "time_interval": 0, "compression": ""},
    "loss_function": "tokens",
    "loader_name": "synthetic_token_rows",
    "loader": {"minibatch_size": 8, "vocab": 64, "seq_len": 32,
               "n_train": 64, "n_valid": 16},
    # the tiny preset (CPU): widths of a toy, the mechanism whole
    "model": {"vocab": 64, "dim": 32, "heads": 4, "kv_heads": 2,
              "head_dim": 8, "dense_hidden": 48, "experts": 8, "top_k": 2,
              "held_count": 8, "hidden": 16, "shared_hidden": 16,
              "n_layers": 5, "window": 8, "q_block": 16,
              "token_block": 64, "learning_rate": 3e-3},
})


class SharedMoELMWorkflow(StandardWorkflow):
    """Loader -> fused trainer -> token evaluator -> decision."""


def build(layers=None, loader_config=None, decision_config=None, **kwargs):
    cfg = root.shared_moe_lm
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
    return SharedMoELMWorkflow(
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
