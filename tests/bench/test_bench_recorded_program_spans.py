"""The recorded chip traces with the program's own ``dstack.*`` host spans
beside the benchmark's ``bench.*`` ones: every per-layer reader the cells
run reads the same value with and without them, and where the program's
spans are kept, they name the device's idle gaps.

``mamba2-1.3b.chat-burst.trace.json.gz`` is one tick of that cell on one
v5e, reduced as ``reduce_trace`` does but keeping the ``dstack.*`` spans
too: an arrival handed over, a packed prefill whose token-count read
waits on it, the decode and its token read-back, observe and pump."""
import dataclasses
from types import SimpleNamespace

import pytest

from benchpath import BENCH, load_run
from harness import trace as tr
from harness.serve import TickRecord
from harness.stats import ClientRecord

# each benchmark span and the program's span that opens inside it
PROGRAM_SPAN = {"bench.execute": "dstack.engine.execute",
                "bench.plan": "dstack.planner.build",
                "bench.observe": "dstack.planner.observe",
                "bench.deliver": "dstack.gateway.deliver"}


def _load(name):
    return tr.load(str(BENCH / "testdata" / f"{name}.trace.json.gz"))


def _with_program_spans(d):
    """``d`` with a ``dstack.*`` span nested in each ``bench.*`` span
    (the recorded trace of a program that opened none)."""
    extra = [(PROGRAM_SPAN[n], s + 1000, d - 2000)
             for n, s, d in d["spans"] if n in PROGRAM_SPAN]
    return {**d, "spans": sorted(d["spans"] + extra, key=lambda e: e[1])}


def _without_program_spans(d):
    return {**d, "spans": [e for e in d["spans"]
                           if not e[0].startswith("dstack.")]}


def _context(run, cell_name, d):
    """A reader's context over a recorded trace: its ticks are the
    ``bench.execute`` spans cut to the traced interval, each decoding 20
    rows and prefilling 256 tokens of one admitted request, and one
    request keeps the chip loaded all through."""
    from repro.serving.engine import EngineStats
    cell = run.load_cell(cell_name)
    t0, t1 = d["window"]
    ticks = [TickRecord((max(s, t0) - t0) / 1e9, (min(s + n, t1) - t0) / 1e9,
                        1e-3, [600] * 20, [(0, 256)], [0], 5e-4)
             for name, s, n in d["spans"] if name == "bench.execute"]
    drive = SimpleNamespace(
        ticks=ticks, w0=-10.0, w1=10.0, drain_end=10.0, t0=0.0,
        gw=SimpleNamespace(streams={}),
        records={0: ClientRecord(-1.0, True, token_times=[9.0])},
        delivered={0: -0.5})
    stats = dataclasses.asdict(EngineStats())
    ctx = run.Context(
        cell.conf["model"],
        run.load_module(BENCH / "models" / f"{cell.conf['reference']}.py"),
        run.load_json(BENCH / "peaks.json")["devices"]["TPU v5 lite"],
        drive, {0: SimpleNamespace(prompt_len=256)},
        {"w0": stats, "w1": {**stats, "prefill_tokens": 256 * len(ticks),
                             "host_syncs": 2 * len(ticks)},
         "compiles": 0},
        False, trace=d, trace_perf=(0.0, t0), stalls=[])
    for k in (BENCH / "kernels").glob("*.py"):
        ctx.kernels[k.stem] = run.load_module(k)
    return cell, ctx


def _readings(run, cell_name, d):
    cell, ctx = _context(run, cell_name, d)
    return {e["name"]: run.load_module(run.reader_file(e["name"])).read(ctx)
            for e in cell.per_layer}


@pytest.mark.parametrize("cell", ["olmo-1b.chat-steady",
                                  "mamba2-1.3b.chat-burst"])
def test_readers_read_the_same_with_and_without_program_spans(cell):
    run = load_run()
    d = _load(cell)
    with_spans = (d if any(e[0].startswith("dstack.") for e in d["spans"])
                  else _with_program_spans(d))
    assert any(e[0].startswith("dstack.") for e in with_spans["spans"])
    without = _readings(run, cell, _without_program_spans(d))
    assert without == _readings(run, cell, with_spans)
    # the readings are readings (in the one mamba2-1.3b tick the prefill
    # also runs once, so the decode step is ambiguous and left out)
    sfx = "dense" if cell.startswith("olmo") else "ssm"
    assert without[f"device.idle_share.loaded.{sfx}"] is not None
    assert (without[f"engine.decode_ms.{sfx}"] is not None) == (
        sfx == "dense")


def test_program_spans_name_the_idle_gaps():
    """Where the reduced trace keeps the program's spans, the innermost one
    open over a gap names it."""
    d = _with_program_spans(_load("olmo-1b.chat-steady"))
    t0, t1 = d["window"]
    ops = tr.first_device(d)["ops"]
    gaps = tr.idle_gaps(ops, d["spans"], t0, t1)
    named = {n for n, _ in gaps}
    assert named and not any(n.startswith("bench.") for n in named)
    assert named <= set(PROGRAM_SPAN.values()) | {"host.other"}


# the program's spans the readers of engine and gateway host time match
PROGRAM_NAMES = {"dstack.tick", "dstack.engine.execute",
                 "dstack.engine.host_sync", "dstack.gateway.pump",
                 "dstack.gateway.deliver"}


def test_recorded_trace_pins_the_program_spans_the_readers_match():
    """One mamba2-1.3b tick: its execute span less its two host syncs
    (the prefill's token count, the decode's read-back) is the engine's
    host time; pump and deliver wholly inside the interval the gateway's.
    A change in what the program names its spans fails here."""
    run = load_run()
    d = _load("mamba2-1.3b.chat-burst")
    names = {e[0] for e in d["spans"]}
    assert PROGRAM_NAMES <= names
    assert {"dstack.planner.build", "dstack.planner.observe",
            "dstack.engine.prefill", "dstack.engine.decode"} <= names
    execute = [e for e in d["spans"] if e[0] == "dstack.engine.execute"]
    syncs = [e for e in d["spans"] if e[0] == "dstack.engine.host_sync"]
    assert len(execute) == 1 and len(syncs) == 2
    (_, s, n), = execute
    own = n - sum(e[2] for e in syncs)
    ctx = SimpleNamespace(trace=d)
    engine = run.load_module(run.reader_file("engine.host_ms_per_tick"))
    assert engine.read(ctx) == pytest.approx(own / 1e6)
    gateway = run.load_module(run.reader_file("gateway.host_ms_per_tick"))
    t0, t1 = d["window"]
    gw = sum(e[2] for e in d["spans"]
             if e[0] in ("dstack.gateway.pump", "dstack.gateway.deliver")
             and t0 <= e[1] and e[1] + e[2] <= t1)
    assert gateway.read(ctx) == pytest.approx(gw / 1e6)
    # the engine's host work outweighs its waits' share of the tick only
    # inside the prefill: 12.0 ms of 87.1
    assert 11.5 < own / 1e6 < 12.5


def test_recorded_program_spans_name_every_idle_gap():
    d = _load("mamba2-1.3b.chat-burst")
    t0, t1 = d["window"]
    ops = tr.first_device(d)["ops"]
    gaps = tr.idle_gaps(ops, d["spans"], t0, t1)
    assert gaps and all(n.startswith("dstack.") or n == "host.other"
                        for n, _ in gaps)
    # the longest: host packing of the packed prefill before its enqueue
    assert gaps[0][0] == "dstack.engine.prefill"
    assert tr.busy_ns(ops, t0, t1) + sum(g for _, g in gaps) == t1 - t0
