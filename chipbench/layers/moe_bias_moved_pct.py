"""Of the assignments the router made in the window's prefills and update forwards (the block's `moe_bias_movable`
on its `iter` span), the share that the selection bias moved: kept by `score + bias` and not by the unbiased scores
(`moe_bias_moved`). 0 means the mechanism runs idle; a program or policy without the counters gives `None`."""

from layers._program_record import window_spans


def read(run):
    found = [s["counters"] for s in window_spans(run, "iter") or [] if "moe_bias_movable" in s["counters"]]
    made = sum(c["moe_bias_movable"] for c in found)
    return 100.0 * sum(c["moe_bias_moved"] for c in found) / made if made else None
