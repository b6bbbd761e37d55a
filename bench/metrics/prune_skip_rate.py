"""prune_skip_rate: the share of summary blocks phase B did not execute
over the window's pruned batches. A batch that took the pruned path
executed the blocks its `prune.phase_b` span counts (`kept`); a batch
that fell back (window delta of `prune_batches_total` under any
`fallback` label but `none`) executed all of them."""


def read(window):
    phase_b = [s for s in window.spans if s["name"] == "prune.phase_b"]
    n_blocks = {s["attrs"].get("n_blocks") for s in window.spans
                if s["name"] in ("prune.phase_a", "prune.phase_b")}
    batches = window.counter_delta("prune_batches_total")
    if not batches or len(n_blocks) != 1:
        return None
    nb = n_blocks.pop()
    fallback = window.counter_delta("prune_batches_total", fallback="!none")
    executed = sum(s["attrs"]["kept"] for s in phase_b) + fallback * nb
    return 1.0 - executed / (batches * nb)
