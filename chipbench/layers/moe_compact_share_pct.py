"""Of the routed layer's calls in the window that could move only the head of their sorted rows (the block's
`moe_compactable_calls` on its `iter` span: prefill's and the update's forwards, one a layer and sequence), the
share that did (`moe_compact_calls`); the others' experts held more rows than the head has, and they moved all."""

from layers._program_record import window_spans


def read(run):
    found = [s["counters"] for s in window_spans(run, "iter") or [] if "moe_compactable_calls" in s["counters"]]
    could = sum(c["moe_compactable_calls"] for c in found)
    return 100.0 * sum(c["moe_compact_calls"] for c in found) / could if could else None
