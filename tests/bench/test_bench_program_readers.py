"""The readers of the program's own stamps, counters and spans, on
hand-made inputs: what each reads, what it leaves out, and that a program
without the stamp, counter or span gives nothing rather than raising."""
from types import SimpleNamespace

import pytest

from benchpath import load_run
from harness.stats import ClientRecord

T0 = 100.0          # the gateway's epoch on the host's perf_counter clock


def _reader(name):
    run = load_run()
    return run.load_module(run.reader_file(name))


def _req(**stamps):
    return SimpleNamespace(arrival=0.0, **stamps)


def _ctx(reqs, in_window, stalls=()):
    """A context whose drive served ``reqs`` ({rid: request}); the rids in
    ``in_window`` were due in the window."""
    drive = SimpleNamespace(
        t0=T0,
        gw=SimpleNamespace(streams={rid: SimpleNamespace(req=r)
                                    for rid, r in reqs.items()}),
        records={rid: ClientRecord(T0, rid in in_window) for rid in reqs})
    return SimpleNamespace(drive=drive, stalls=list(stalls))


STAMPED = {
    1: _req(delivered=1.00, scheduled=1.00, first_token=1.20),
    2: _req(delivered=2.00, scheduled=2.10, first_token=2.40),
    3: _req(delivered=3.00, scheduled=3.30, first_token=3.40),
    4: _req(delivered=4.00, scheduled=4.05, first_token=-1.0),   # no token
    5: _req(delivered=5.00, scheduled=5.90, first_token=6.00),   # a stall
    6: _req(delivered=6.00, scheduled=9.00, first_token=9.50),   # tail
}
# the profiler's start, which held the host, while request 5 waited
STALLS = [(T0 + 5.2, T0 + 5.8)]


def test_queue_wait_is_the_median_of_scheduled_minus_delivered():
    read = _reader("planner.queue_wait_p50_ms").read
    ctx = _ctx(STAMPED, in_window={1, 2, 3, 4, 5}, stalls=STALLS)
    # waits of 1-4 (5 spans a stall, 6 is not in the window): 0, 100,
    # 300, 50 ms; the nearest-rank median is the second smallest
    assert read(ctx) == pytest.approx(50.0)
    ctx = _ctx(STAMPED, in_window={2, 3, 6})
    assert read(ctx) == pytest.approx(300.0)


def test_first_token_after_schedule_is_the_median_of_its_stamps():
    read = _reader("engine.first_token_after_schedule_p50_ms").read
    ctx = _ctx(STAMPED, in_window={1, 2, 3, 4, 5}, stalls=STALLS)
    # 1-3 and 5 (4 has no token yet; 5's stall ended before its first
    # chunk): 200, 300, 100, 100 ms
    assert read(ctx) == pytest.approx(100.0)
    ctx = _ctx(STAMPED, in_window={1, 2, 3})
    assert read(ctx) == pytest.approx(200.0)


@pytest.mark.parametrize("name", ["planner.queue_wait_p50_ms",
                                  "engine.first_token_after_schedule_p50_ms"])
def test_stamp_readers_read_nothing_from_a_program_without_stamps(name):
    read = _reader(name).read
    # a request as a program without the stamps keeps it: no delivered or
    # scheduled attribute, first_token at the tick's start
    old = {1: SimpleNamespace(arrival=0.0, first_token=1.2)}
    assert read(_ctx(old, in_window={1})) is None
    # nothing in the window
    assert read(_ctx(STAMPED, in_window=set())) is None


def test_host_syncs_per_tick_is_the_window_delta_over_its_ticks():
    read = _reader("engine.host_syncs_per_tick").read
    ticks = [object()] * 40

    def ctx(w0, w1, n):
        return SimpleNamespace(stats={"w0": w0, "w1": w1},
                               window_ticks=lambda: ticks[:n])
    assert read(ctx({"host_syncs": 10}, {"host_syncs": 70}, 40)) == 1.5
    assert read(ctx({"host_syncs": 10}, {"host_syncs": 70}, 0)) is None
    # a program whose EngineStats has no host_syncs counter
    assert read(ctx({"decode_steps": 1}, {"decode_steps": 9}, 40)) is None
    assert read(SimpleNamespace(stats={}, window_ticks=list)) is None


# two ticks inside the traced interval [1000, 9000); the third ends past it
SPANS = [
    ("bench.execute", 990, 3000),
    ("dstack.gateway.deliver", 1000, 40),
    ("dstack.tick", 1100, 2000),
    ("dstack.planner.build", 1110, 90),
    ("dstack.engine.execute", 1200, 1500),
    ("dstack.engine.decode", 1210, 200),
    ("dstack.engine.host_sync", 1420, 1000),
    ("dstack.planner.observe", 2800, 100),
    ("dstack.gateway.pump", 3150, 60),
    ("dstack.tick", 4000, 3000),
    ("dstack.engine.execute", 4100, 2500),
    ("dstack.engine.prefill", 4110, 600),
    ("dstack.engine.host_sync", 4200, 300),
    ("dstack.engine.host_sync", 5000, 1200),
    ("dstack.gateway.pump", 7100, 100),
    ("dstack.tick", 8000, 2000),
    ("dstack.engine.execute", 8100, 1500),
    ("dstack.engine.host_sync", 8200, 1000),
]
TRACE = {"window": [1000, 9000], "devices": {}, "spans": SPANS}


def test_engine_host_ms_is_execute_less_its_host_syncs():
    read = _reader("engine.host_ms_per_tick").read
    # (1500 - 1000) and (2500 - 300 - 1200) ns; the third tick ends late
    assert read(SimpleNamespace(trace=TRACE)) == pytest.approx(
        (500 + 1000) / 2 / 1e6)


def test_gateway_host_ms_is_pump_and_deliver_per_tick():
    read = _reader("gateway.host_ms_per_tick").read
    assert read(SimpleNamespace(trace=TRACE)) == pytest.approx(
        (40 + 60 + 100) / 2 / 1e6)


@pytest.mark.parametrize("name", ["engine.host_ms_per_tick",
                                  "gateway.host_ms_per_tick"])
def test_span_readers_read_nothing_without_the_programs_spans(name):
    read = _reader(name).read
    bench_only = {**TRACE, "spans": [s for s in SPANS
                                     if s[0].startswith("bench.")]}
    assert read(SimpleNamespace(trace=bench_only)) is None
