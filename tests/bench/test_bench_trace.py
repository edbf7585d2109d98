"""The reduction from a device trace to busy time, idle gaps and per-kernel
time, on hand-made events and on a trace recorded on the chip."""
import pytest

from benchpath import BENCH
from harness import trace as tr

OPS = [("fusion.1", 0, 10), ("paged_kernel", 5, 10), ("fusion.2", 30, 5),
       ("ssd_kernel", 50, 20), ("fusion.1", 65, 10)]
SPANS = [("bench.execute", 0, 40), ("bench.plan", 40, 8),
         ("bench.execute", 48, 30)]


def test_busy_is_the_union_of_op_intervals():
    # [0, 15) + [30, 35) + [50, 75) inside [0, 100)
    assert tr.busy_ns(OPS, 0, 100) == 15 + 5 + 25
    # cut to the window
    assert tr.busy_ns(OPS, 8, 60) == 7 + 5 + 10


def test_idle_gaps_are_named_by_the_innermost_open_span():
    gaps = tr.idle_gaps(OPS, SPANS, 0, 100)
    assert gaps == [("host.other", 25), ("bench.execute", 15),
                    ("bench.plan", 15)]
    assert sum(d for _, d in gaps) == 100 - tr.busy_ns(OPS, 0, 100)


def test_per_operation_totals_and_kernel_matching():
    tot = dict(tr.op_totals(OPS, 0, 100))
    assert tot == {"fusion.1": 20, "paged_kernel": 10, "fusion.2": 5,
                   "ssd_kernel": 20}
    assert [e[0] for e in tr.matching(OPS, ("paged",))] == ["paged_kernel"]


def test_overlap_of_two_interval_sets():
    assert tr.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 5 + 5
    assert tr.overlap_ns([(0, 10)], [(10, 20)]) == 0


RECORDED = "olmo-1b.chat-steady.trace.json.gz"
# what the readers match in the recorded trace: the decode step, and the
# one Pallas call per layer of each executable
DECODE_STEP = "jit__lambda(5405628257002391173)"
PAGED_CALL = "%closed_call.9 custom-call bf16[64,16,1,128] tpu_custom_call"
PREFILL_CALL = "%closed_call.9 custom-call bf16[1,16,512,128] tpu_custom_call"


def _recorded():
    """The recorded trace, its device, and its two ticks (both decode, the
    second also prefills), taken from the ``bench.execute`` spans."""
    d = tr.load(str(BENCH / "testdata" / RECORDED))
    ticks = [(s, s + n, True) for name, s, n in d["spans"]
             if name == "bench.execute"]
    return d, tr.first_device(d), ticks


def test_recorded_chip_trace():
    """A second of olmo-1b.chat-steady on one v5e (two decode steps),
    reduced by ``reduce_trace`` and cut: the decode step is the executable
    run once in each decoding tick, its Pallas calls are the paged decode
    kernel, one per layer and step, and busy time plus idle gaps fill the
    window."""
    d, dev, ticks = _recorded()
    t0, t1 = d["window"]
    decode = tr.decode_module(dev["modules"], ticks)
    assert sum(m[0] == decode for m in dev["modules"]) == 2
    kern = tr.kernel_calls(tr.matching(dev["ops"], ("tpu_custom_call",)),
                           dev["modules"], ticks, decode, True, 16, [0, 1])
    assert len(kern) == 2 * 16
    assert sum(e[2] for e in kern) == 722573074
    busy = tr.busy_ns(dev["ops"], t0, t1)
    gaps = tr.idle_gaps(dev["ops"], d["spans"], t0, t1)
    assert busy + sum(g for _, g in gaps) == t1 - t0
    assert busy / (t1 - t0) == pytest.approx(0.99, abs=0.01)
    top = tr.op_totals(dev["ops"], t0, t1)
    assert tr.short(top[1][0]).endswith("tpu_custom_call")


def test_recorded_trace_pins_what_the_readers_match():
    """The executable and the op names the readers take in the recorded
    trace: a change in what they match fails here, not in a number."""
    d, dev, ticks = _recorded()
    ops = tr.matching(dev["ops"], ("tpu_custom_call",))
    decode = tr.decode_module(dev["modules"], ticks)
    assert decode == DECODE_STEP
    paged = tr.kernel_calls(ops, dev["modules"], ticks, decode, True, 16,
                            [0, 1])
    assert {tr.short(e[0]) for e in paged} == {PAGED_CALL}
    prefill = tr.kernel_calls(ops, dev["modules"], ticks, decode, False, 16,
                              [1])
    assert len(prefill) == 16
    assert {tr.short(e[0]) for e in prefill} == {PREFILL_CALL}


def _second_kernel(dev, decode):
    """A second Pallas call in the first run of the decode step."""
    _, s, _ = next(m for m in dev["modules"] if m[0] == decode)
    return dev["ops"] + [("%other custom-call tpu_custom_call", s + 1, 1)]


@pytest.mark.parametrize("fault", ["second_kernel", "missed_call",
                                   "tick_without_kernel", "split_step",
                                   "no_decode_run"])
def test_a_reading_that_would_mix_or_miss_kernels_is_refused(fault):
    d, dev, ticks = _recorded()
    decode = DECODE_STEP
    ops, modules, layers, need = dev["ops"], dev["modules"], 16, [0, 1]
    if fault == "second_kernel":
        ops = _second_kernel(dev, decode)
    elif fault == "missed_call":
        layers = 17
    elif fault == "tick_without_kernel":
        # a third decoding tick whose step holds no Pallas call
        end = max(max(s + n for _, s, n in modules), ticks[-1][1])
        ticks = ticks + [(end + 10, end + 30, True)]
        modules = modules + [(decode, end + 15, 5)]
        need = [0, 1, 2]
    elif fault == "split_step":
        # a second executable as long as the step, once in each tick
        modules = modules + [("jit_other", s + 1, 400_000_000)
                             for _, s, _ in ((None, t[0], None)
                                             for t in ticks)]
    elif fault == "no_decode_run":
        modules = [m for m in modules if m[0] != decode]
    if fault in ("split_step", "no_decode_run"):
        assert tr.decode_module(modules, ticks) is None
        return
    assert tr.decode_module(modules, ticks) == decode
    ops = tr.matching(ops, ("tpu_custom_call",))
    assert tr.kernel_calls(ops, modules, ticks, decode, True, layers,
                           need) is None
