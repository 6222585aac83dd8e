"""The least HBM bytes a step's hyper-connections need, from tokens, the
number of streams and the hidden size, whatever implements them.

One hyper-connection around one sublayer, per token, with the streams X
[n, C] and the sublayer's input u and output y [C] at ``itemsize`` bytes a
channel. Forward: X is read once (its norm, its maps and the read u all come
from that one pass), u written, y read, X' written: (2 n + 2) C. Backward:
dX' and X read once (dX and every map's gradient come from them), y read for
H_post's gradient, dy written, du read, dX written: (3 n + 3) C. The maps
themselves (2 n + n^2 numbers a token) and Phi are a thousandth of that and
count for nothing; neither does a replay of the forward, which is the
program's choice. The floor is bytes over the HBM peak: the matmul with Phi
(2 n C (2 n + n^2) FLOPs a token) is far under it on any chip listed."""
from __future__ import annotations


def connection_bytes_per_token(n: int, hidden: int, itemsize: int = 2) -> int:
    """Forward and backward of one hyper-connection."""
    return (5 * n + 5) * hidden * itemsize


def step_bytes(cfg: dict, tokens: int) -> int:
    """All of a step's: two a layer, the multi-token-prediction module's
    layers included."""
    layers = cfg["num_hidden_layers"] + cfg.get("num_nextn_predict_layers", 0)
    return 2 * layers * tokens * connection_bytes_per_token(
        cfg["hc_mult"], cfg["hidden_size"])
