"""The least bytes one batch of the serving query "the top k of the catalog
the asking users have not rated" must read from the device's memory,
whatever the implementation: closed form, beside ``peaks.py``'s table of
the chip's peaks.

Every catalog row once as the shortlist scores it — ``rank`` int8 values,
one f32 scale, one validity byte — and every excluded id of the batch once
(int32).  Not counted, because an implementation could do without: the
mask the program builds and reads back, the shortlist's second pass over
the score matrix, the f32 rows of the rescore (``k`` of them a query).
"""

from __future__ import annotations


def score_bytes(columns, rank, excluded_ids):
    return columns * (rank + 4 + 1) + 4 * excluded_ids
