"""d2h_ms: the median over the window's ticks of the copy of a tick's
result to the host, in ms: the duration of the completion stage's
`serve.d2h` span, which opens once the tick's device work is done
(`serve.ready`), so it times the copy alone."""
import statistics


def read(window):
    d2h = [s["duration_s"] * 1e3 for s in window.spans
           if s["name"] == "serve.d2h"]
    return statistics.median(d2h) if d2h else None
