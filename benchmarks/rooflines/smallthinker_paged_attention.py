"""The paged attention kernel (``ops/pallas_attention.py``,
``unified_write_attend_pallas``) of SmallThinker, whose layer kinds come
from ``sliding_window_layout`` (a full layer, then three window layers)
and whose 28 query heads run in groups of seven padded to eight: the
count is ``windowed_paged_attention``'s, this file says where a
SmallThinker configuration keeps the numbers it reads."""

from benchmarks.rooflines import windowed_paged_attention


def translated(config: dict) -> dict | None:
    """The configuration under the keys ``windowed_paged_attention.work``
    reads: ``layer_types`` from ``sliding_window_layout`` (1 = a window
    layer). Another family's configuration gives ``None``."""
    layout = config.get("sliding_window_layout")
    if layout is None:
        return None
    return {**config, "layer_types": [
        "sliding_attention" if bit else "full_attention" for bit in layout]}


def work(config: dict, ctx) -> dict | None:
    """``windowed_paged_attention.work`` (its docstring says which way
    each count errs: both LOW) on this family's keys. The operations are
    those of the 28 PUBLISHED query heads: the eighth row of each padded
    group (``padded_q_heads``) multiplies zeros, is never written out and
    is not counted, so the share reads LOW by up to an eighth where a
    step is bound by operations (a chunk), and not at all where it is
    bound by the K/V it reads (a decode step: no K/V byte more is
    read)."""
    mapped = translated(config)
    return None if mapped is None else \
        windowed_paged_attention.work(mapped, ctx)
