"""Operations and bytes the K/V paged attention kernels need for a call
(``ops/pallas/paged_attention.py``: ``paged_decode_sidebuf`` / ``paged_decode``
for decode rows, ``paged_chunk`` for a pass's prompt chunks), from a
configuration's widths and what the program says its rows held: the
arguments of its ``serve/decode/step`` and ``serve/prefill/pass`` spans.

What the algorithm needs, not what the kernel happens to move or compute: a
reading made with these is a lower bound of the kernel's share of its
roofline (a row of a padded bucket, a page's unused tail under a window and
the keys a chunk reads again for every block of its queries count nothing
here).

- A cached token takes ``2 x KV heads x head width`` values a layer in the
  pool's pages (K and V; bfloat16: 4 KiB at Mistral's 8 heads of 128, 1 KiB at
  the 2 heads of 128 of cells 10 and 12). A decode row of a FULL layer reads
  every page that holds a token of its context, whole — a page is what the
  kernel copies — and of a WINDOWED layer the ``min(ctx, window)`` tokens its
  query still sees; its query comes in and its output goes out once, a query
  head's width each.
- A query-key pair costs a query head ``2 x head width`` operations for the
  score and as many for the output: ``4 x query heads x head width`` a pair,
  ``heads / KV heads`` operations a byte of K and V — 4 to 32 in the
  benchmark's configurations against a v5e's 240, so a decode call is bound
  by its bytes.
- A prompt chunk of ``ntok`` tokens behind ``cached`` keys sees, under the
  causal mask, ``ntok x cached + ntok x (ntok + 1) / 2`` pairs (token ``t``
  sees ``min(cached + t + 1, window)`` under a window), reads the keys it
  sees once and moves its queries and outputs once.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, Optional, Sequence, Tuple

from chipbench import models

#: what ``layer_types`` calls a windowed attention layer where a model mixes
#: them with full ones (afmoe); a model whose ``sliding_window`` is set and
#: that names no kinds is windowed in every layer (Mistral)
SLIDING = "sliding_attention"


def widths(config: Dict[str, Any]) -> Dict[str, Any]:
    """The kernels' widths and the layers that run them, from a
    configuration file: the paged cache's layout as the cell's family sizes
    its pool (``families/<family>.py::kv_layout``; the two families of
    ``chipbench/models.py`` attend in every layer over ``hidden_size /
    num_attention_heads`` wide heads), and which of those layers are
    windowed as the engine decides it (a window no context can pass is
    none). Raises ``KeyError`` or ``AttributeError`` for a configuration
    with no K/V pages (latent attention)."""
    if config["family"] in models.FAMILIES:
        layers, kv_heads = (config["num_hidden_layers"],
                            config["num_key_value_heads"])
        head_dim = config["hidden_size"] // config["num_attention_heads"]
    else:
        family = importlib.import_module(
            "chipbench.families." + config["family"])
        layers, kv_heads, head_dim = family.kv_layout(config)
    engine = config["engine"]
    window = config.get("sliding_window")
    if window and engine["state_manager"]["max_context"] <= window:
        window = None
    kinds = config.get("layer_types") or ()
    windowed = 0 if not window else (
        kinds.count(SLIDING) if SLIDING in kinds else layers)
    itemsize = 2                      # the cells' pools are bfloat16
    return {"heads": int(config["num_attention_heads"]),
            "kv_heads": int(kv_heads), "head_dim": int(head_dim),
            "itemsize": itemsize,
            "block_size": int(engine["kv_cache"]["block_size"]),
            # a token's K and V in one layer's pages
            "token_bytes": 2 * int(kv_heads) * int(head_dim) * itemsize,
            "full_layers": int(layers) - windowed,
            "windowed_layers": windowed,
            "window": int(window) if windowed else None}


def _pair_flops(w: Dict[str, Any]) -> int:
    return 4 * w["heads"] * w["head_dim"]


def _row_io(w: Dict[str, Any]) -> int:
    """Bytes of one query token's heads in and its output's out."""
    return 2 * w["heads"] * w["head_dim"] * w["itemsize"]


def decode_call(w: Dict[str, Any], rows: float, ctx: float, pages: float,
                ctx_window: Optional[float] = None) -> Tuple[float, float]:
    """``(operations, bytes)`` of one decode call of one layer over ``rows``
    live rows that hold ``ctx`` tokens in ``pages`` whole pages between
    them: a full layer's call, or with ``ctx_window`` (the rows'
    ``min(ctx, window)``, summed) a windowed layer's."""
    if ctx_window is None:
        keys, read = ctx, pages * w["block_size"]
    else:
        keys = read = ctx_window
    return (float(keys) * _pair_flops(w),
            float(read) * w["token_bytes"] + float(rows) * _row_io(w))


def chunk_call(w: Dict[str, Any], ntok: Sequence[int], cached: Sequence[int],
               window: Optional[int] = None) -> Tuple[float, float]:
    """``(operations, bytes)`` of one ``paged_chunk`` call of one layer over
    a pass's live slots: slot ``i`` holds ``ntok[i]`` prompt tokens behind
    ``cached[i]`` keys in pages; with ``window`` a windowed layer's call."""
    pairs = keys = tokens = 0
    for n, c in zip(ntok, cached):
        n, c = int(n), int(c)
        if not n:
            continue
        tokens += n
        if window is None:
            pairs += n * c + n * (n + 1) // 2
            keys += c + n
        else:
            pairs += sum(min(c + t + 1, window) for t in range(n))
            keys += min(c + n, window + n - 1)
    return (float(pairs) * _pair_flops(w),
            float(keys) * w["token_bytes"] + float(tokens) * _row_io(w))
