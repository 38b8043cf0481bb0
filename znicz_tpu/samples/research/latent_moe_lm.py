"""A language model of latent attention and shared and routed experts,
with a multi-token prediction module.

The mechanism, not a model's name: an embedding, then layers of two
residual entries each (``x += f(RMS(x))``, no norm on the way out), a plain
head, and after it a module that predicts the id two positions ahead.

The attention branch is latent attention (``latent_attention``): the
queries come from a normed projection of rank ``q_rank``, the keys and
values from a normed latent of rank ``kv_rank``; a head's query and key are
a ``nope_dim`` part with no position and a ``rope_dim`` part turned by the
position in the row, the keys' turned part one for all the heads; values
are ``v_dim`` wide.  Causal, cut at document boundaries.

The feed-forward branch: a gated MLP in the first ``dense_layers`` layers;
in the others a router over all ``experts`` whose scores are sigmoids.  A
token goes to the ``top_k`` largest of score plus selection bias; its
weights are the chosen scores alone, divided by their sum and times
``route_scale``.  The ``moe`` entry adds what the experts held here give
(``held``: this chip's share of an expert-parallel group) to a shared
expert that every token passes.  After every optimizer step the bias of
every expert moves by ``balance_rate`` toward the mean load; it takes no
gradient.  There is no auxiliary loss.

The head (``head``): final norm, untied output product, cross-entropy
against the next id.  It passes its normed state ``h`` on down the chain to
the multi-token prediction module (``mtp_input``): ``m = [RMS(E[t_{i+1}]) |
RMS(h_i)] W`` with ``E`` the embedding's own table, then one decoder layer
of the trunk's expert-layer form, then a second head (``mtp_head``) with a
gain of its own and the first head's output weight, graded against the id
two positions ahead where the three positions lie in one document.  The
objective is the first head's mean loss plus ``mtp_weight`` times the
second's.  The embedding and the output weight are one leaf each, which
both heads' gradients reach.

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


def make_layers(vocab=64, dim=32, heads=4, q_rank=24, kv_rank=16,
                nope_dim=8, rope_dim=8, v_dim=16, dense_hidden=48,
                experts=8, top_k=2, held_first=0, held_count=8, hidden=16,
                shared_hidden=16, n_layers=3, dense_layers=1,
                rope_base=10000.0, eps=1e-5, stddev=0.02, route_scale=1.8,
                balance_rate=1e-3, mtp_weight=0.3, q_block=None,
                token_block=None, learning_rate=3e-4, weights_decay=0.1,
                adam_beta1=0.9, adam_beta2=0.95, adam_eps=1e-8):
    """The ``layers`` config: ``n_layers`` layers of the trunk, the head,
    and the multi-token prediction module with its head."""
    bwd = {"learning_rate": learning_rate, "weights_decay": weights_decay,
           "weights_decay_bias": 0.0, "solvers": ["adamw"],
           "adam_beta1": adam_beta1, "adam_beta2": adam_beta2,
           "adam_eps": adam_eps}

    def norm(name):
        return {"type": "rmsnorm", "name": name, "->": {"eps": eps},
                "<-": dict(bwd)}

    def block(tag, dense):
        if dense:
            feed = [{"type": "gated_mlp", "name": tag + "_mlp",
                     "->": {"hidden": dense_hidden,
                            "weights_stddev": stddev},
                     "<-": dict(bwd)}]
        else:
            feed = [
                {"type": "router", "name": tag + "_router",
                 "->": {"experts": experts, "weights_stddev": stddev},
                 "<-": dict(bwd)},
                {"type": "moe", "name": tag + "_moe",
                 "->": {"router": tag + "_router", "experts": experts,
                        "top_k": top_k, "held": [held_first, held_count],
                        "hidden": hidden, "shared_hidden": shared_hidden,
                        "activation": "silu", "score": "sigmoid",
                        "route_scale": route_scale,
                        "balance_rate": balance_rate,
                        "weights_stddev": stddev},
                 "<-": dict(bwd)}]
        return [
            {"type": "residual", "remat": True, "layers": [
                norm(tag + "_norm1"),
                {"type": "latent_attention", "name": tag + "_attn",
                 "->": {"heads": heads, "q_rank": q_rank,
                        "kv_rank": kv_rank, "nope_dim": nope_dim,
                        "rope_dim": rope_dim, "v_dim": v_dim,
                        "rope_base": rope_base, "eps": eps,
                        "q_block": q_block, "weights_stddev": stddev},
                 "<-": dict(bwd)}]},
            {"type": "residual", "remat": True, "layers":
                [norm(tag + "_norm2")] + feed}]

    layers = [{"type": "embedding", "name": "embed",
               "->": {"vocab": vocab, "dim": dim, "weights_stddev": stddev},
               "<-": dict(bwd)}]
    for i in range(n_layers):
        layers.extend(block("l%d" % i, i < dense_layers))
    layers.append({"type": "lm_head", "name": "head",
                   "->": {"vocab": vocab, "eps": eps,
                          "token_block": token_block,
                          "weights_stddev": stddev},
                   "<-": dict(bwd)})
    layers.append({"type": "mtp_input", "name": "mtp_input",
                   "->": {"tied": "embed", "eps": eps,
                          "weights_stddev": stddev},
                   "<-": dict(bwd)})
    layers.extend(block("mtp", False))
    layers.append({"type": "lm_head", "name": "mtp_head",
                   "->": {"vocab": vocab, "eps": eps, "tied": "head",
                          "shift": 2, "loss_weight": mtp_weight,
                          "token_block": token_block},
                   "<-": dict(bwd)})
    return layers


root.latent_moe_lm.update({
    "decision": {"fail_iterations": 50, "max_epochs": 20},
    "snapshotter": {"prefix": "latent_moe_lm", "interval": 1,
                    "time_interval": 0, "compression": ""},
    "loss_function": "tokens",
    "loader_name": "synthetic_token_rows",
    "loader": {"minibatch_size": 8, "vocab": 64, "seq_len": 32,
               "n_train": 64, "n_valid": 16},
    # the tiny preset (CPU): widths of a toy, the mechanism whole
    "model": {"vocab": 64, "dim": 32, "heads": 4, "q_rank": 24,
              "kv_rank": 16, "nope_dim": 8, "rope_dim": 8, "v_dim": 16,
              "dense_hidden": 48, "experts": 8, "top_k": 2,
              "held_count": 8, "hidden": 16, "shared_hidden": 16,
              "n_layers": 3, "q_block": 16, "token_block": 64,
              "learning_rate": 3e-3},
})


class LatentMoELMWorkflow(StandardWorkflow):
    """Loader -> fused trainer -> token evaluator -> decision."""


def build(layers=None, loader_config=None, decision_config=None, **kwargs):
    cfg = root.latent_moe_lm
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
    return LatentMoELMWorkflow(
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
