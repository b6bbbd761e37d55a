"""The readers of the program's wait spans: `d2h_ms` and
`traffic_wait_share` on a hand-built window, and nothing (None, not an
error) from a program that records no such span."""
import os

import pytest

from bench import run as brun

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def window(spans, seconds=2.0):
    return brun.Window(ticks=[], max_batch=16, counters0={}, counters1={},
                       spans=spans, trace={"busy_s": 1.0}, config={},
                       seconds=seconds, peaks={})


def span(name, t_start, duration_s, **attrs):
    return {"name": name, "t_start": t_start, "duration_s": duration_s,
            "attrs": attrs}


SPANS = [
    span("serve.idle", 0.00, 0.30),
    span("serve.tick", 0.30, 0.01, tick=0),
    span("serve.transfer", 0.31, 0.05, tick=0),
    span("serve.ready", 0.31, 0.04, tick=0),
    span("serve.d2h", 0.35, 0.004, tick=0),
    span("serve.fill_wait", 0.32, 0.002),
    span("serve.pipeline_full", 0.33, 0.02),
    span("serve.tick", 0.36, 0.01, tick=1),
    span("serve.d2h", 0.40, 0.010, tick=1),
    span("serve.d2h", 0.45, 0.006, tick=2),
]


def test_d2h_ms_is_the_median_copy():
    read = brun.load_reader("d2h_ms", ROOT)
    assert read(window(SPANS)) == pytest.approx(6.0)


def test_traffic_wait_share_sums_idle_and_fill_waits():
    read = brun.load_reader("traffic_wait_share", ROOT)
    # serve.pipeline_full is a wait for the device, not for traffic
    assert read(window(SPANS)) == pytest.approx((0.30 + 0.002) / 2.0)
    # the idle span after the last tick counts only up to the close
    tail = SPANS + [span("serve.idle", 1.9, 5.0)]
    assert read(window(tail)) == pytest.approx((0.30 + 0.002 + 0.1) / 2.0)


def test_a_dispatcher_that_never_waited_reads_zero():
    read = brun.load_reader("traffic_wait_share", ROOT)
    busy = [s for s in SPANS if s["name"] not in ("serve.idle",
                                                  "serve.fill_wait")]
    assert read(window(busy)) == 0.0


@pytest.mark.parametrize("name", ["d2h_ms", "traffic_wait_share"])
def test_readers_are_silent_without_their_spans(name):
    read = brun.load_reader(name, ROOT)
    older = [s for s in SPANS if s["name"] in ("serve.tick",
                                               "serve.transfer")]
    assert read(window(older)) is None
    assert read(window([])) is None
