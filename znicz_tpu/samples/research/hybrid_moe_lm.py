"""A language model of state-space mixers, attention and shared and routed
experts, laid out by a pattern string.

The mechanism, not a model's name: an embedding, then one residual entry a
layer (``x += f(RMS(x))``, no norm on the way out), a plain head.  The
pattern names each layer's ``f``, a letter a layer:

* ``M``: Mamba-2's mixer (``ssd``): ``[z | xBC | dt] = x W_in``; ``xBC``
  through a depthwise causal conv of width ``conv`` and silu, split into
  ``x`` (``ssd_heads`` heads of ``ssd_head_dim``) and ``B``, ``C``
  (``groups`` of ``state``); the state-space recurrence ``S_t = exp(dt_t
  A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` computed in
  chunks of ``chunk``; ``y * silu(z)`` normed over each group; ``W_out``.
  A document start resets the state and the conv.
* ``E``: a router over all ``experts`` whose scores are sigmoids; a token
  goes to the ``top_k`` largest of score plus selection bias, its weights
  the chosen scores alone, divided by their sum and times
  ``route_scale``; the ``moe`` entry adds what the experts held here give
  (``held``: this chip's share of an expert-parallel group) to a shared
  expert every token passes.  Experts are not gated: ``relu(x W_u)^2
  W_d``.  After every optimizer step the bias moves by ``balance_rate``
  toward the mean load; it takes no gradient.
* ``*``: attention with ``heads`` query heads on ``kv_heads`` key-value
  heads, causal inside documents, with no position encoding.

The head: final norm, untied output product, cross-entropy against the
next id.  Rows are packed documents, as in :mod:`looped_lm`.  It trains
through the fused trainer only.  The preset here is tiny, for the CPU;
``make_layers`` takes the widths of a real one (``benchmarks/configs/``
holds a published configuration).
"""

from znicz_tpu.core.config import root
# registers the loader the preset names
from znicz_tpu.samples.research.looped_lm import (  # noqa: F401
    SyntheticTokenRows)
from znicz_tpu.standard_workflow import StandardWorkflow


def make_layers(pattern="MEM*E", first=0, count=None, vocab=64, dim=32,
                ssd_heads=4, ssd_head_dim=8, state=8, groups=2, conv=4,
                chunk=8, heads=4, kv_heads=2, head_dim=8, experts=8,
                top_k=2, held_first=0, held_count=8, hidden=16,
                shared_hidden=24, eps=1e-5, stddev=0.02, out_stddev=None,
                route_scale=2.5, balance_rate=1e-3, dt_min=0.001,
                dt_max=0.1, dt_floor=1e-4, q_block=None, token_block=None,
                learning_rate=3e-4, weights_decay=0.1, adam_beta1=0.9,
                adam_beta2=0.95, adam_eps=1e-8):
    """The ``layers`` config: layers ``first .. first + count - 1`` of
    ``pattern`` (all of it from ``first`` where ``count`` is None), then
    the head.  ``out_stddev`` is the mixers' ``W_out`` init (``stddev``
    where None)."""
    bwd = {"learning_rate": learning_rate, "weights_decay": weights_decay,
           "weights_decay_bias": 0.0, "solvers": ["adamw"],
           "adam_beta1": adam_beta1, "adam_beta2": adam_beta2,
           "adam_eps": adam_eps}
    kinds = pattern[first:] if count is None \
        else pattern[first:first + count]
    if set(kinds) - set("ME*"):
        raise ValueError("pattern %r: M, E and * only" % (pattern,))

    def branch(i, kind):
        tag = "l%d" % i
        if kind == "M":
            return [{"type": "ssd", "name": tag + "_ssd",
                     "->": {"heads": ssd_heads, "head_dim": ssd_head_dim,
                            "state": state, "groups": groups,
                            "conv": conv, "chunk": chunk, "eps": eps,
                            "dt_min": dt_min, "dt_max": dt_max,
                            "dt_floor": dt_floor, "weights_stddev": stddev,
                            "out_stddev": out_stddev or stddev},
                     "<-": dict(bwd)}]
        if kind == "*":
            return [{"type": "attention", "name": tag + "_attn",
                     "->": {"heads": heads, "kv_heads": kv_heads,
                            "head_dim": head_dim, "rope": False,
                            "q_block": q_block, "weights_stddev": stddev},
                     "<-": dict(bwd)}]
        return [
            {"type": "router", "name": tag + "_router",
             "->": {"experts": experts, "weights_stddev": stddev},
             "<-": dict(bwd)},
            {"type": "moe", "name": tag + "_moe",
             "->": {"router": tag + "_router", "experts": experts,
                    "top_k": top_k, "held": [held_first, held_count],
                    "hidden": hidden, "shared_hidden": shared_hidden,
                    "gated": False, "activation": "relu2",
                    "score": "sigmoid", "route_scale": route_scale,
                    "balance_rate": balance_rate,
                    "weights_stddev": stddev},
             "<-": dict(bwd)}]

    layers = [{"type": "embedding", "name": "embed",
               "->": {"vocab": vocab, "dim": dim, "weights_stddev": stddev},
               "<-": dict(bwd)}]
    for i, kind in enumerate(kinds, first):
        layers.append({"type": "residual", "remat": True, "layers": [
            {"type": "rmsnorm", "name": "l%d_norm" % i, "->": {"eps": eps},
             "<-": dict(bwd)}] + branch(i, kind)})
    layers.append({"type": "lm_head", "name": "head",
                   "->": {"vocab": vocab, "eps": eps,
                          "token_block": token_block,
                          "weights_stddev": stddev},
                   "<-": dict(bwd)})
    return layers


root.hybrid_moe_lm.update({
    "decision": {"fail_iterations": 50, "max_epochs": 20},
    "snapshotter": {"prefix": "hybrid_moe_lm", "interval": 1,
                    "time_interval": 0, "compression": ""},
    "loss_function": "tokens",
    "loader_name": "synthetic_token_rows",
    "loader": {"minibatch_size": 8, "vocab": 64, "seq_len": 32,
               "n_train": 64, "n_valid": 16},
    # the tiny preset (CPU): widths of a toy, the mechanism whole
    "model": {"pattern": "MEM*E", "vocab": 64, "dim": 32, "ssd_heads": 4,
              "ssd_head_dim": 8, "state": 8, "groups": 2, "chunk": 8,
              "heads": 4, "kv_heads": 2, "head_dim": 8, "experts": 8,
              "top_k": 2, "held_count": 8, "hidden": 16,
              "shared_hidden": 24, "q_block": 16, "token_block": 64,
              "learning_rate": 3e-3},
})


class HybridMoELMWorkflow(StandardWorkflow):
    """Loader -> fused trainer -> token evaluator -> decision."""


def build(layers=None, loader_config=None, decision_config=None, **kwargs):
    cfg = root.hybrid_moe_lm
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
    return HybridMoELMWorkflow(
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
