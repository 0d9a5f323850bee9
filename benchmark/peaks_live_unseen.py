"""The least bytes a fold of users over their WHOLE rating histories must
move through the device's memory, whatever implements it: closed form,
beside ``peaks.py``'s table of the chip's peaks and ``peaks_unseen.py``.

Every REAL rating's item row once — ``rank`` float32 values; the padding a
program adds up to its compiled width is its own affair — and every solved
row written once.  Not counted, because an implementation could do without:
the ids and stars (12 bytes a rating beside 4 * rank), the gathered rows
written out and read back before the Gram build, the Gram matrices
(``rank`` squared floats a user, on chip in a fused kernel), the passes a
multi-pass float32 multiply makes over its operands.
"""

from __future__ import annotations


def fold_bytes(ratings, rows, rank):
    return 4 * rank * (ratings + rows)
