"""A looped language model: one block of layers run several times.

The mechanism, not a model's name: an embedding, then a block of
pre/post-normed attention and gated-MLP layers applied ``passes`` times
in sequence with ONE set of weights (``{"type": "loop"}``), a head after
every pass (final norm, exit gate, untied output product), and a loss
that mixes the passes' cross-entropies by a learned exit distribution
less an entropy bonus.  Rows are packed documents: attention is causal
and cut at document boundaries, and every position but a document's last
is graded.  It trains through the fused trainer only (``--fused``): token
rows resident on the device as integers, AdamW, counts of graded tokens
to the evaluator and decision.

The preset here is tiny, for the CPU; ``make_layers`` takes the widths
of a real one (``benchmarks/configs/`` holds a published configuration).
"""

import numpy

from znicz_tpu.core.config import root
from znicz_tpu.loader.tokens import TokenRowsLoader, pack_rows
from znicz_tpu.standard_workflow import StandardWorkflow


def make_layers(vocab=64, dim=32, heads=4, kv_heads=4, head_dim=8,
                hidden=64, n_layers=2, passes=2, rope_base=1e6, eps=1e-6,
                stddev=0.02, exit_entropy_weight=0.05, q_block=None,
                token_block=None, learning_rate=3e-4, weights_decay=0.1,
                adam_beta1=0.9, adam_beta2=0.95, adam_eps=1e-8):
    """The ``layers`` config of a looped language model."""
    bwd = {"learning_rate": learning_rate, "weights_decay": weights_decay,
           "weights_decay_bias": 0.0, "solvers": ["adamw"],
           "adam_beta1": adam_beta1, "adam_beta2": adam_beta2,
           "adam_eps": adam_eps}

    def norm(name):
        return {"type": "rmsnorm", "name": name, "->": {"eps": eps},
                "<-": dict(bwd)}

    def block(i):
        return [
            {"type": "residual", "remat": True, "layers": [
                norm("l%d_norm1" % i),
                {"type": "attention", "name": "l%d_attn" % i,
                 "->": {"heads": heads, "kv_heads": kv_heads,
                        "head_dim": head_dim, "rope_base": rope_base,
                        "q_block": q_block,
                        "weights_stddev": stddev},
                 "<-": dict(bwd)},
                norm("l%d_norm2" % i)]},
            {"type": "residual", "remat": True, "layers": [
                norm("l%d_norm3" % i),
                {"type": "gated_mlp", "name": "l%d_mlp" % i,
                 "->": {"hidden": hidden, "weights_stddev": stddev},
                 "<-": dict(bwd)},
                norm("l%d_norm4" % i)]}]

    body = [entry for i in range(n_layers) for entry in block(i)]
    body.append({"type": "lm_head", "name": "head",
                 "->": {"vocab": vocab, "eps": eps,
                        "token_block": token_block,
                        "exit_entropy_weight": exit_entropy_weight,
                        "weights_stddev": stddev},
                 "<-": dict(bwd)})
    return [{"type": "embedding", "name": "embed",
             "->": {"vocab": vocab, "dim": dim, "weights_stddev": stddev},
             "<-": dict(bwd)},
            {"type": "loop", "times": passes, "layers": body}]


class SyntheticTokenRows(TokenRowsLoader):
    """Packed documents of a learnable toy language: inside a document
    the next id is ``(3 * id + 1) % vocab``, a tenth of the time a random
    one; document lengths are lognormal."""

    MAPPING = "synthetic_token_rows"

    def __init__(self, workflow, **kwargs):
        super(SyntheticTokenRows, self).__init__(workflow, **kwargs)
        self.vocab = int(kwargs.get("vocab", 64))
        self.seq_len = int(kwargs.get("seq_len", 32))
        self.n_train = int(kwargs.get("n_train", 32))
        self.n_valid = int(kwargs.get("n_valid", 8))
        self.doc_median = float(kwargs.get("doc_median", 12))

    def load_data(self):
        r = numpy.random.RandomState(0x70c5)
        n = self.n_train + self.n_valid
        total = n * self.seq_len
        lengths = numpy.clip(r.lognormal(numpy.log(self.doc_median), 0.8,
                                         total // 2 + 1), 2,
                             self.seq_len).astype(numpy.int64)
        ids = numpy.empty(total, numpy.int64)
        noise = r.rand(total) < 0.1
        draws = r.randint(0, self.vocab, total)
        ends = set(numpy.cumsum(lengths).tolist())
        for i in range(total):
            if i == 0 or i in ends or noise[i]:
                ids[i] = draws[i]
            else:
                ids[i] = (3 * ids[i - 1] + 1) % self.vocab
        self.set_rows(*pack_rows(lengths, ids, n, self.seq_len),
                      n_valid=self.n_valid)


root.looped_lm.update({
    "decision": {"fail_iterations": 50, "max_epochs": 20},
    "snapshotter": {"prefix": "looped_lm", "interval": 1,
                    "time_interval": 0, "compression": ""},
    "loss_function": "tokens",
    "loader_name": "synthetic_token_rows",
    "loader": {"minibatch_size": 8, "vocab": 64, "seq_len": 32,
               "n_train": 64, "n_valid": 16},
    # the tiny preset (CPU): widths of a toy, the mechanism whole
    "model": {"vocab": 64, "dim": 32, "heads": 4, "kv_heads": 4,
              "head_dim": 8, "hidden": 64, "n_layers": 2, "passes": 2,
              "q_block": 16, "token_block": 64, "learning_rate": 3e-3},
})


class LoopedLMWorkflow(StandardWorkflow):
    """Loader -> fused trainer -> token evaluator -> decision."""


def build(layers=None, loader_config=None, decision_config=None, **kwargs):
    cfg = root.looped_lm
    loader_cfg = cfg.loader.as_dict()
    loader_cfg.update(loader_config or {})
    decision_cfg = cfg.decision.as_dict()
    decision_cfg.update(decision_config or {})
    kwargs.setdefault("loss_function", cfg.loss_function)
    if kwargs.get("fused") is None:
        # the one path this model has (no unit of the graph path knows the
        # token-sequence kinds), so it is on with or without ``--fused``
        kwargs["fused"] = {}
    snap_cfg = cfg.snapshotter.as_dict()
    snap_cfg.update(kwargs.pop("snapshotter_config", None) or {})
    return LoopedLMWorkflow(
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
