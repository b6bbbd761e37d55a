"""prune_fallback_share: the window's pruned batches that fell back to
the full scan (delta of `prune_batches_total` under any `fallback` label
but `none`), over all of its pruned batches."""


def read(window):
    batches = window.counter_delta("prune_batches_total")
    if not batches:
        return None
    return window.counter_delta("prune_batches_total",
                                fallback="!none") / batches
