"""Operations and bytes the DeepFM step needs, from its shapes alone.

Model FLOPs count the matrix products of the forward and backward passes
(2 per multiply-add forward, twice that backward: 6 per weight per sample),
nothing recomputed, nothing for element-wise work. Bytes are what the
ALGORITHM has to move through HBM in one step, not what this program moves:
Adam on an embedding table changes only the rows a batch touches, so the
need is per id, never per table row. The dense sweep the program does today
is printed beside it by the resident driver, as `dense_sweep_bytes`.
"""

from __future__ import annotations

NUM_DENSE, NUM_CAT = 13, 26


def _ints(text):
    return [int(x) for x in str(text).split(",") if x]


def tower_macs(model_params: dict) -> int:
    d = int(model_params["embedding_dim"])
    widths = [NUM_CAT * d + NUM_DENSE] + _ints(model_params["hidden"]) + [1]
    return sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def model_flops_per_sample(model_params: dict) -> float:
    """Forward + backward. The linear unit over the 13 continuous features
    is counted; FM's second order is element-wise and is not."""
    return 6.0 * (tower_macs(model_params) + NUM_DENSE)


def dense_parameter_count(model_params: dict) -> int:
    d = int(model_params["embedding_dim"])
    widths = [NUM_CAT * d + NUM_DENSE] + _ints(model_params["hidden"]) + [1]
    return sum(a * b + b for a, b in zip(widths[:-1], widths[1:])) + NUM_DENSE + 2


def step_bytes(model_params: dict, batch: int) -> float:
    """Least HBM traffic of one step of `batch` samples: each of the
    batch*26 ids reads its row once (forward), its gradient row is written
    and read once, and Adam reads and writes parameter and both moments of
    the row (no id counted as shared: shapes do not say how many are); the
    dense parameters with their moments and gradient once each way; the
    batch itself once."""
    row = 4 * (int(model_params["embedding_dim"]) + 1)
    ids = batch * NUM_CAT
    return float(ids * row * (1 + 2 + 6)
                 + dense_parameter_count(model_params) * 4 * 7
                 + batch * 4 * (NUM_DENSE + NUM_CAT + 2))


def dense_sweep_bytes(model_params: dict, table_rows: int) -> float:
    """What a dense Adam over the whole table moves: gradient written and
    read, parameter and two moments read and written (7 passes over the
    table's logical bytes)."""
    return 7.0 * table_rows * 4 * (int(model_params["embedding_dim"]) + 1)


def placement_bytes(model_params: dict, batch: int, table_rows: int) -> float:
    """What the placement of one step's embedding gradients needs: the
    sorted stream of batch*26 gradient rows and their ids read once, the
    dense gradient of the table written once."""
    row = 4 * (int(model_params["embedding_dim"]) + 1)
    return float(batch * NUM_CAT * (row + 4) + table_rows * row)
