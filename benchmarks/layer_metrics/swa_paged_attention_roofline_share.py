"""Paged-attention kernel, grouped and windowed (``ops/paged_attention.py``
under ``models/swa_moe.py``): the least time the chip could take for the
attention of the traced span's dispatches, over the time the kernel's events
took on the device in the span.

Per dispatch the least time is max(operations / peak FLOP/s, bytes / peak
bytes/s) of its attention alone (``costs_swa_moe.attention_work``): the
operations over the keys its rows attend (a window layer's at most the
window) and the pages read by kind x the bytes a page stands for over its
kind's layers. Decode is bound by its bytes; a 512-row chunk over a long
context by its operations.

The kernel's events are ``paged_attention.<n> f32[<tables>,<rows>,<width>]``
in ``obs.trace["device_ops"]`` (the ledger writes the same name with ``_``
for every other character), and the harness keeps the ten longest
operations only. Decode's kernel has one shape (``rows`` = the query heads a
K/V head); a prefill's has one a lane bucket (``tables`` = lanes x the tiles
a chunk is cut into, ``rows`` = a tile's positions x the query heads a K/V
head). A shape's dispatches are counted, on both sides, only if its event is
among the operations kept: the share is of the kept shapes' work over the
kept shapes' time. None where no kernel event is kept (lifting the cut to
ten is a `benchmark` PR's), on another family, or off the chip."""

import re

from benchmarks.costs_swa_moe import (SwaMoeShape, attention_work,
                                      roofline_seconds)

_EVENT = re.compile(r"^paged_attention\.\d+[ _]f32[\[_](\d+)[,_](\d+)[,_]")


def read(obs):
    s = obs.shape
    if (obs.peaks is None or obs.trace is None
            or not isinstance(s, SwaMoeShape)):
        return None
    engine = obs.cell.config["engine"]
    block, chunk = int(engine["block_size"]), int(engine["prefill_chunk"])
    group = s.n_head // s.n_kv_head
    kept = {}                   # (phase, lane bucket) -> seconds on the device
    for name, seconds in obs.trace["device_ops"]:
        found = _EVENT.match(name)
        if not found:
            continue
        tables, rows = int(found.group(1)), int(found.group(2))
        if rows == group:       # one query row a table, its group of heads
            key = ("decode", tables)
        else:                   # tiles of rows // group positions a chunk
            key = ("prefill_chunk", tables * (rows // group) // chunk)
        kept[key] = kept.get(key, 0.0) + seconds
    lo, hi = obs.trace["span_ns"]
    least = 0.0
    counted = set()
    for r in obs.steps:
        key = (r["phase"], r.get("lanes"))
        if key in kept and lo <= r["start_ns"] < hi:
            work = attention_work(s, r, block)
            if work:
                least += roofline_seconds(work, obs.peaks)
                counted.add(key)
    seconds = sum(kept[key] for key in counted)
    if not least or not seconds:
        return None
    return 100.0 * least / seconds
