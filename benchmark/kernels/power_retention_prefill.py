"""What one call of ``power_retention_prefill`` needs, from shapes alone:
the chunked-prefill kernel of a stack of retention layers
(``ops/power_retention.py``). One call is one layer of one prefill dispatch;
``rows`` is the dispatch's rows and ``tokens`` its VALID prompt tokens (its
rows', summed; the census gives no split, so the rows share them evenly), pad
left out. Kept with the benchmark, as ``flops.py`` is, so that no PR that
claims a gain can change the count.

``cfg`` is the configuration file's published block. Counted is the
mathematics' least, whatever the program's tiling: a valid token is folded
into the state and the summed keys once (``Hkv`` heads of ``2 D (d + 1)``),
and each of its ``Hq`` queries takes the CHEAPER of the two forms of the same
sum: the state (``2 D (d + 1)``) or the positions before it in its dispatch
and itself pair by pair (scores and weighted values, ``4 d`` a pair; the
cheaper below ``D (d + 1) / 2 d`` = 4160 positions, so for every token of a
chunk of 4096). What a later chunk's queries need of the chunks BEFORE their
dispatch, through the state or pair by pair, the census does not give and the
count leaves out: it is a floor of the least, and the share errs low. ``D`` as
in ``power_retention_decode.py``. The bytes are the chunk's queries, keys,
values and results in the model's two bytes and a head's state read and
written once a row: a sixth of the operations' time at a full chunk, so the operations
bound the call.
"""

from __future__ import annotations

from benchmark.kernels import power_retention_decode as state


def _heads(cfg: dict):
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads") or hq
    return hq, hkv, cfg.get("head_dim") or cfg["hidden_size"] // hq


def operations(cfg: dict, rows: float, tokens: float) -> float:
    hq, hkv, d = _heads(cfg)
    by_state = 2.0 * state.feature_dim(cfg) * (d + 1)
    a_pair = 4.0 * d
    n = tokens / max(rows, 1.0)
    # places 0 .. paired - 1 of a row attend pair by pair, the rest the state
    paired = min(n, by_state / a_pair)
    queries = a_pair * paired * (paired + 1) / 2 + (n - paired) * by_state
    return tokens * hkv * by_state + rows * hq * queries


def bytes_read(cfg: dict, rows: float, tokens: float) -> float:
    hq, hkv, d = _heads(cfg)
    return (
        tokens * (2 * hq + 2 * hkv) * d * 2.0
        + rows * 2.0 * state.state_bytes_per_row(cfg)
    )
