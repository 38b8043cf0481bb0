"""Rows of token ids — the full-batch loader of a token-sequence model.

A row is ``seq`` token ids with, at every position, a label (the next id
inside the same document, ``-1`` where none is graded: a document's last
position, the row's last position) and a segment id (which document of
the row the position belongs to; attention is cut where it changes).
Documents are concatenated and cut into rows, so a document that meets a
row's end is split into two segments (:func:`pack_rows`).

The fill is the stock :class:`FullBatchLoader` copy, so the fused
trainer's resident path engages: ids, labels and segments are placed on
the device once, as integers, and each minibatch is gathered there by
its row indices (``FusedNet.set_dataset``); the put-off fill of PR 26
covers validation minibatches too.
"""

import numpy

from znicz_tpu.loader.base import FullBatchLoader, TEST, VALID, TRAIN


def pack_rows(doc_lengths, ids, n_rows, seq):
    """(ids, labels, segments), each ``(n_rows, seq)`` int32, of the
    stream ``ids`` (``n_rows * seq`` tokens of documents laid end to end,
    ``doc_lengths`` long each; the lengths must cover the stream)."""
    total = int(n_rows) * int(seq)
    ends = numpy.cumsum(numpy.asarray(doc_lengths, dtype=numpy.int64))
    if not len(ends) or ends[-1] < total:
        raise ValueError("documents cover %d of %d tokens"
                         % (ends[-1] if len(ends) else 0, total))
    doc = numpy.searchsorted(ends, numpy.arange(total), side="right")
    ids = numpy.asarray(ids, dtype=numpy.int32).reshape(n_rows, seq)
    doc = doc.reshape(n_rows, seq)
    labels = numpy.full((n_rows, seq), -1, numpy.int32)
    same = doc[:, 1:] == doc[:, :-1]
    labels[:, :-1] = numpy.where(same, ids[:, 1:], -1)
    segments = (doc - doc[:, :1] + 1).astype(numpy.int32)
    return ids, labels, segments


class TokenRowsLoader(FullBatchLoader):
    """Full-batch loader over int32 ``ids (N, S)``, ``labels (N, S)`` and
    ``segments (N, S)``; ``load_data`` of a subclass hands them to
    :meth:`set_rows` in the layout [TEST | VALID | TRAIN]."""

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("normalization_type", "none")
        super(TokenRowsLoader, self).__init__(workflow, **kwargs)
        #: per-position labels and segment ids of every row (what the
        #: trainer places beside the ids)
        self.token_labels = None
        self.token_segments = None

    def set_rows(self, ids, labels, segments, n_valid, n_test=0):
        ids = numpy.ascontiguousarray(ids, dtype=numpy.int32)
        labels = numpy.ascontiguousarray(labels, dtype=numpy.int32)
        segments = numpy.ascontiguousarray(segments, dtype=numpy.int32)
        if not (ids.shape == labels.shape == segments.shape
                and ids.ndim == 2):
            raise ValueError("ids, labels and segments are (N, S) alike")
        self.original_data.reset(ids)
        self.token_labels, self.token_segments = labels, segments
        # one entry a row (its S labels): the stock fill copies
        # ``labels[sel]`` into the (B, S) minibatch buffer
        del self._original_labels[:]
        self._original_labels.extend(labels)
        self._labels_array = labels
        self.class_lengths[TEST] = int(n_test)
        self.class_lengths[VALID] = int(n_valid)
        self.class_lengths[TRAIN] = len(ids) - int(n_valid) - int(n_test)

    @property
    def unique_labels_count(self):
        raise AttributeError("token rows carry no class count")

    def create_minibatch_data(self):
        # ids stay integers whatever the engine's precision type
        shape = (self.max_minibatch_size,) + tuple(
            self.original_data.shape[1:])
        self.minibatch_data.reset(numpy.zeros(shape, numpy.int32))
        self.minibatch_labels.reset(numpy.full(shape, -1, numpy.int32))
