"""Causal attention cut at document boundaries and, where the layer has
one, at a window of keys, with grouped key-value heads: one application.

Required FLOPs: the four projections, and for the scores and the weighted
sum only the (query, key) pairs a row attends (``ent["pairs"]``: ``j <= i``
inside one document and inside the window, counted exactly from the mix's
documents), 2 products of ``head_dim`` a pair and query head.  Whether the
layer turns its queries and keys by position changes no matrix work.  Least
bytes: the input and output, q, k and v once (k and v at the key-value
heads' width), the weights once; the scores need not cross HBM."""
from benchmarks.layer_costs import causal_attention

MXU = True
parts = causal_attention.parts
cost = causal_attention.cost
