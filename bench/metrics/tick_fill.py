"""tick_fill: the share of tick slots the window's ticks filled,
sum of `TickStats.batch` over ticks × max_batch."""


def read(window):
    ticks = [t for t in window.ticks if t.batch > 0]
    if not ticks:
        return None
    return sum(t.batch for t in ticks) / (len(ticks) * window.max_batch)
