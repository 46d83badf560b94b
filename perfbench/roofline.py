"""Operations and bytes of the serving work, from shapes alone.

Byte arithmetic follows the engine's paged layout: a page holds
`page_size` rows, and the decode kernel reads each live sequence's pages
up to the one holding its last row (ceil(len / page_size) pages).  These
functions are the benchmark's own copy, so a later change to the program
cannot move the yardstick.
"""
from __future__ import annotations

from perfbench import reference as R


def kv_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """K and V bytes of one cached token in one layer."""
    m = R.dims(cfg)
    return 2 * m["Hkv"] * m["hd"] * itemsize


def read_rows(lengths, page_size: int) -> int:
    """Rows one decode step reads for live sequence lengths."""
    return sum(-(-int(n) // page_size) * page_size for n in lengths if n > 0)


def weight_bytes(cfg: dict) -> int:
    """Bytes of every linear (layers and unembedding) at its stored width,
    plus the norms: what one decode step reads once."""
    m = R.dims(cfg)
    n_lin = sum(a * b for a, b in R.linear_shapes(cfg).values())
    if cfg["weights"] == "int8":
        scales = sum(b for _, b in R.linear_shapes(cfg).values()) * 4
        layer = n_lin + scales + 2 * m["d"] * 2
        head = m["d"] * m["V"] + m["V"] * 4
    else:
        layer = 2 * n_lin + 2 * m["d"] * 2
        head = 2 * m["d"] * m["V"]
    return m["L"] * layer + head + 2 * m["d"]


def tick_bytes(cfg: dict, lengths, page_size: int, n_slots: int) -> int:
    """Least bytes one decode step moves: the weights once, the live K/V
    pages read (every layer) and one new K/V row written per sequence,
    plus the embedding rows gathered."""
    m = R.dims(cfg)
    live = [n for n in lengths if n > 0]
    kv = kv_row_bytes(cfg) * m["L"] * (read_rows(live, page_size) + len(live))
    return weight_bytes(cfg) + kv + n_slots * m["d"] * 2


def paged_decode_bytes(cfg: dict, lengths, page_size: int,
                       n_slots: int) -> int:
    """Least bytes the paged-decode kernel moves in one step, all layers:
    live K/V pages, and q and the output for every slot (bf16)."""
    m = R.dims(cfg)
    qo = 2 * n_slots * m["H"] * m["hd"] * 2
    return m["L"] * (kv_row_bytes(cfg) * read_rows(lengths, page_size) + qo)


def token_flops(cfg: dict, context: int, logits: bool) -> int:
    """Model FLOPs of one token that attends `context` positions
    (itself included): 2 per weight of every layer linear, 4 * H * hd per
    attended position per layer (QK and PV), and the unembedding when the
    token's logits are used."""
    m = R.dims(cfg)
    n_lin = sum(a * b for a, b in R.linear_shapes(cfg).values())
    f = m["L"] * (2 * n_lin + 4 * m["H"] * m["hd"] * context)
    return f + (2 * m["d"] * m["V"] if logits else 0)


def prefill_flops(cfg: dict, n: int) -> int:
    """A prompt of n tokens, prefilled from position 0; logits only for
    the last row."""
    m = R.dims(cfg)
    n_lin = sum(a * b for a, b in R.linear_shapes(cfg).values())
    attn = 4 * m["H"] * m["hd"] * n * (n + 1) // 2
    return m["L"] * (2 * n_lin * n + attn) + 2 * m["d"] * m["V"]
