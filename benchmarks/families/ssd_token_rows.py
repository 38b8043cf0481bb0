"""Family ``ssd_token_rows``: ``balanced_token_rows``' job (packed rows, a
routed language model whose expert layers carry a selection bias that the
load moves, AdamW, compared **under one choice of experts**) for a model
whose mixers are mostly **state-space (Mamba-2) layers** beside attention,
and whose experts are relu^2 and not gated.

Data, loader, packing, the captured entry, the optimizer's leaves, the
capture's copies, the reference's steps, the comparison and the unit of
work are the balanced family's own, by import: the reference the
configuration names (``reference/ssd_moe_lm.py``) brings the mixer, the
experts and the faults, and the balanced family drives any reference that
keeps its contract.  What differs, and why files alone could not give it,
is the readings (``READINGS``): the mechanisms' own faults, each planted in
the bf16 reference put in the program's place.  The state's reset and the
conv's cut at a document start are what the mix's documents (several
starts a row) make the numbers see: a fault there moves the outputs of
every position after a start, up to the end of the document.
"""

from benchmarks.families import balanced_token_rows as balanced

make_data = balanced.make_data
loader = balanced.loader
ENTRY = balanced.ENTRY
STATE_LEAVES = balanced.STATE_LEAVES
first_epoch = balanced.first_epoch
release = balanced.release
rows_trained = balanced.rows_trained
row_tokens = balanced.row_tokens
plan = balanced.plan
feed = balanced.feed
keep = balanced.keep
fetch = balanced.fetch
leaf_numbers = balanced.leaf_numbers
follow = balanced.follow
graded = balanced.graded
numbers = balanced.numbers
in_place = balanced.in_place
GRADED = balanced.GRADED

#: (reading, mode, fault, least chips): the bf16 witness, the fp8 control,
#: and faults planted in the bf16 reference put in the program's place
READINGS = (("bf16", "bf16", None, 1), ("fp8", "fp8", None, 1)) + tuple(
    (fault, "bf16", fault, 1) for fault in (
        "reset_left_out", "conv_across_documents", "norm_whole_width",
        "d_skip_left_out", "relu_not_squared", "shared_left_out",
        "bias_left_out_of_choice", "centring_left_out"))
