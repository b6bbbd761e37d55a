"""queue_wait_ms: the median over the window's ticks of the scheduler's
`TickStats.wait_ms` (the tick's head request, submit to dispatch)."""
import statistics


def read(window):
    waits = [t.wait_ms for t in window.ticks if t.batch > 0]
    return statistics.median(waits) if waits else None
