"""The bytes one score call must move whatever implements it: every
DISTINCT table row its batch reads, once, and the batch's real cells
(an id and a value each). A scorer that gathers raw ids, one that
gathers the host unique's fitted slots and a kernel of its own are
read against the same least, so ``validation_score_roofline`` compares
them (``readers/score_roofline.py``)."""

from __future__ import annotations

ID_BYTES = 4        # int32 feature id (or slot index)
VALUE_BYTES = 4     # float32 feature value
SCORE_BYTES = 4     # float32 score an example, written


def score_call_min_bytes(distinct_rows: float, row_dim: int, cells: float,
                         examples: float) -> float:
    """``distinct_rows`` rows of ``row_dim`` float32 read once, ``cells``
    real (id, value) pairs read once, ``examples`` scores written."""
    return (distinct_rows * row_dim * 4
            + cells * (ID_BYTES + VALUE_BYTES)
            + examples * SCORE_BYTES)
