"""traffic_wait_share: the share of the window in which the dispatcher
had no tick to send, the summed durations of its `serve.idle` (empty
queue) and `serve.fill_wait` (a partial head tick held for up to
`max_wait_ms`) spans that start in the window, over its seconds. A span
is cut at the window's close, which comes at most the window's seconds
after the first span starts (the idle span after the last tick runs on
until shutdown). A dispatcher that never waited reads 0; a program that
records none of the serving path's wait spans (`serve.ready` and
`serve.d2h` open on every traced tick) reads nothing."""
WAITS = ("serve.idle", "serve.fill_wait")
TRACED = WAITS + ("serve.pipeline_full", "serve.ready", "serve.d2h")


def read(window):
    if not any(s["name"] in TRACED for s in window.spans):
        return None
    close = min(s["t_start"] for s in window.spans) + window.seconds
    return sum(min(s["duration_s"], close - s["t_start"])
               for s in window.spans if s["name"] in WAITS) / window.seconds
