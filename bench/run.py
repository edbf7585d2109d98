"""The benchmark: one cell of BENCHMARK.json, one process, one chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chip the cell asks
for. The run checks the device, builds the cell's configuration with
weights made on the device from the seed, warms every executable the
cell's traffic can reach, then serves the traffic mix through the
program's wall-clock gateway: a lead-in, the measured window of
``--seconds``, and a drain of bounded length. With ``--trace 0`` it
reports the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics from a profiler trace of a few seconds of the window. Either way
it then checks what the window served against a plain float32 reference.
The last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error.

Everything that belongs to one configuration, mix, per-layer metric or
kernel is a file of its own, found by name: ``bench/configs/<config>.json``
(with its reference ``bench/models/<reference>.py``),
``bench/traffic/<mix>.json``, ``bench/metrics/<metric>.py`` and
``bench/kernels/<kernel>.py``. A metric split by a dotted suffix between
cells that are held to different bounds (``tbt_p95_ms.dense``,
``tbt_p95_ms.ssm``) is the quantity before the suffix: the end-to-end
statistic of that name, or the reader of that name (``reader_file``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

TRACE_SETTLE_S = 1.0     # after start_trace, before the traced interval
TRACE_S = 4.0            # length of the traced interval


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """A module from a file under bench/, by path (metric and kernel file
    names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_file(name: str) -> Path:
    """The reader of per-layer metric ``name``: ``bench/metrics/<name>.py``,
    or, where there is none, the reader of ``name`` without its last dotted
    part. A quantity read alike in cells that report different end-to-end
    metrics is split by a suffix (``engine.decode_ms.dense`` and
    ``.ssm``), each part with its own entry, and one reader."""
    path = BENCH / "metrics" / f"{name}.py"
    if path.exists() or "." not in name:
        return path
    return reader_file(name.rsplit(".", 1)[0])


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict                 # the configuration file
    mix: Dict                  # the traffic file
    e2e: List[Dict]            # end-to-end metric entries for this cell
    per_layer: List[Dict]      # per-layer metric entries for this cell


def load_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(ROOT / confs[w["config"]]["file"])
    mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")

    def mine(entries):
        return [m for m in entries if name in m.get("workloads", [name])]
    return Cell(name, int(w["chips"]), conf, mix, mine(bench["end_to_end"]),
                mine(bench["per_layer"]))


# ----------------------------------------------------------------- device
def device_info(chips: int, check: bool) -> Dict:
    """The device as JAX reports it; refuses anything but enough TPU chips
    of a kind in bench/peaks.json."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not check:
        return info
    if info["platform"] != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform "
                         f"{info['platform']!r}); the benchmark never runs "
                         f"on another platform")
    if info["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{info['count']}")
    if info["kind"] not in load_json(BENCH / "peaks.json")["devices"]:
        raise SystemExit(f"bench: device kind {info['kind']!r} is not in "
                         f"bench/peaks.json")
    return info


class CompileWatch:
    """Host times of JAX backend compiles (persistent-cache loads
    included), from JAX's monitoring events; while ``log`` is on, the names
    of the functions compiled as well."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.times: List[float] = []
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def log(self, on: bool) -> None:
        import jax
        jax.config.update("jax_log_compiles", on)
        if on and not hasattr(self, "_handler"):
            watch = self

            class H(logging.Handler):
                def emit(self, record):
                    watch.names.append(record.getMessage()[:160])
            self._handler = H(logging.WARNING)
            logging.getLogger("jax").addHandler(self._handler)


# ------------------------------------------------------------------ model
def program_config(conf: Dict, reduced: bool):
    """The program's configuration of this model, checked against the
    sizes the configuration file states (``reduced``: the program's CPU
    smoke size, with the file's sizes taken from it instead)."""
    from repro.configs import get_config
    cfg = get_config(conf["registry"])
    m = dict(conf["model"])
    if reduced:
        cfg = cfg.reduced()
        for field, key in conf["engine_config"].items():
            m[key] = getattr(cfg, field)
        return cfg, m
    for field, key in conf["engine_config"].items():
        if getattr(cfg, field) != m[key]:
            raise SystemExit(f"bench: the program's {conf['registry']} has "
                             f"{field}={getattr(cfg, field)}, the "
                             f"configuration file {key}={m[key]}")
    if str(cfg.dtype) != conf["dtype"]:
        raise SystemExit(f"bench: the program serves {cfg.dtype}, the "
                         f"configuration states {conf['dtype']}")
    return cfg, m


def check_layout(cfg, weights) -> None:
    """The benchmark's weights must have exactly the tree and shapes the
    program's model takes."""
    import jax
    from repro.models.registry import build_model
    want = jax.eval_shape(lambda: build_model(cfg).init(
        jax.random.PRNGKey(0), jax.numpy.dtype(cfg.dtype)))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("bench: the benchmark's weight tree does not match "
                         "the program's model")


# ---------------------------------------------------------------- the run
@dataclasses.dataclass
class Context:
    """What the per-layer metric readers read."""
    m: Dict
    family: object             # bench/models/<reference>.py
    peaks: Optional[Dict]      # None off the chip: no device metric then
    drive: object              # harness.serve.Drive
    arrivals: Dict[int, object]
    stats: Dict[str, Dict]     # engine counters at window start / end
    recompute: bool            # continuations recompute their prefix
    trace: Optional[Dict] = None
    trace_perf: Optional[tuple] = None  # (perf_counter, trace ns) anchor
    stalls: List[tuple] = dataclasses.field(default_factory=list)
    kernels: Dict[str, object] = dataclasses.field(default_factory=dict)

    def to_ns(self, perf: float) -> int:
        p0, ns0 = self.trace_perf
        return int(ns0 + (perf - p0) * 1e9)

    def traced_perf(self):
        """The traced interval on the host's perf_counter clock."""
        t0, t1 = self.trace["window"]
        return self.trace_perf[0], self.trace_perf[0] + (t1 - t0) / 1e9

    def traced_ticks(self):
        """Ticks executed wholly inside the traced interval."""
        t0, t1 = self.trace["window"]
        return [t for t in self.drive.ticks
                if self.to_ns(t.start) >= t0 and self.to_ns(t.end) <= t1]

    def window_ticks(self):
        return [t for t in self.drive.ticks
                if self.drive.w0 <= t.start < self.drive.w1]


@dataclasses.dataclass
class Prepared:
    cell: Cell
    info: Dict
    peaks: Optional[Dict]
    watch: CompileWatch
    cfg: object
    m: Dict
    family: object
    weights: Dict
    eng: object
    warm: Dict


def prepare(workload: str, seed: int, *, device_check: bool = True,
            reduced: bool = False, overrides: Optional[Dict] = None
            ) -> Prepared:
    """Set-up: device check, compile cache, weights, engine, warm-up."""
    cell = load_cell(workload)
    if overrides:
        cell.conf = {**cell.conf,
                     "serving": {**cell.conf["serving"],
                                 **overrides.get("serving", {})},
                     "check": {**cell.conf["check"],
                               **overrides.get("check", {})}}
        cell.mix = {**cell.mix, **overrides.get("mix", {})}
    import jax
    info = device_info(cell.chips, device_check)
    peaks = (load_json(BENCH / "peaks.json")["devices"].get(info["kind"])
             if device_check else None)
    if device_check:
        # the persistent cache at the checkout's fixed path (the program's
        # own choice, or JAX_COMPILATION_CACHE_DIR), holding every program
        from repro.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = CompileWatch()

    from harness import serve
    cfg, m = program_config(cell.conf, reduced)
    family = load_module(BENCH / "models" / f"{cell.conf['reference']}.py")
    weights = family.weights(m, seed, jax.numpy.dtype(cfg.dtype))
    check_layout(cfg, weights)
    eng = serve.build_engine(cfg, weights, cell.conf["serving"])
    warm = serve.warm(eng, cell.conf["serving"], cell.mix)
    warm["compiles"] = len(watch.times)
    return Prepared(cell, info, peaks, watch, cfg, m, family, weights, eng,
                    warm)


def sweep(workload: str, seed: int, seconds: float, scales) -> None:
    """Serve the cell's mix at each rate multiple in turn, on one warm
    engine, and print one line of end-to-end numbers per rate: for finding
    the highest rate the system sustains (never part of a measured run)."""
    from harness import serve, stats, traffic
    p = prepare(workload, seed)
    mix, sv = p.cell.mix, p.cell.conf["serving"]
    lead = float(mix["lead_in_s"])
    for k in scales:
        arrivals = traffic.schedule(mix, seed, seconds, k)
        prompts = traffic.prompt_tokens(arrivals, seed, p.m["vocab_size"])
        drive = serve.Drive(p.eng, sv["planner"], p.cfg.name, False)
        depth = {}

        async def during(d):
            import asyncio
            await asyncio.sleep(max(0.0, d.w1 - time.perf_counter()))
            depth["queue"] = len(d.planner.queue)
            depth["resident"] = p.eng.n_slots - p.eng.free_slots
        # no drain: the sweep reads the backlog the window leaves
        drive.run(arrivals, prompts, lead, lead + seconds, lead + seconds,
                  during)
        lim = mix["limits"]
        e = stats.end_to_end(list(drive.records.values()), drive.w0,
                             drive.w1, drive.drain_end, lim["ttft_ms"] / 1e3,
                             lim["tbt_ms"] / 1e3)
        win = sorted((r for r in drive.records.values() if r.in_window),
                     key=lambda r: r.due)
        firsts = [r.ttft(drive.drain_end) for r in win]
        n = len(firsts) // 3
        print(json.dumps({
            "rate_scale": k, "rate_rps": float(mix["arrival"]["rate_rps"]) * k,
            **{key: e[key] for key in ("ttft_p95_ms", "tbt_p95_ms",
                                       "goodput_rps", "output_tok_per_s",
                                       "attempted", "failed")},
            "ttft_p50_ms": stats.percentile(firsts, 0.5) * 1e3,
            "ttft_first_third_mean_ms": 1e3 * sum(firsts[:n]) / max(1, n),
            "ttft_last_third_mean_ms": 1e3 * sum(firsts[-n:]) / max(1, n),
            "tbt_p50_ms": stats.percentile(
                [g for r in win for g in r.gaps()], 0.5) * 1e3,
            **depth, "setup_compiles": p.warm["compiles"]}), flush=True)
        drive.close()


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        device_check: bool = True, reduced: bool = False,
        overrides: Optional[Dict] = None, dump: Optional[str] = None,
        patch=None, control: bool = False) -> Dict:
    """One run; returns the result object. ``reduced``, ``overrides``
    (``{"serving": ..., "mix": ..., "check": ...}``) and ``patch`` (called with the
    engine before serving) are for the CPU tests; ``dump`` (write the
    traced run's trace) and ``control`` (also read the controls' widest
    gaps on the same sample) for sizing the benchmark on the chip."""
    import jax
    from harness import serve, stats, traffic
    p = prepare(workload, seed, device_check=device_check, reduced=reduced,
                overrides=overrides)
    cell, info, peaks, watch = p.cell, p.info, p.peaks, p.watch
    cfg, m, family, weights, eng, warm = (p.cfg, p.m, p.family, p.weights,
                                          p.eng, p.warm)
    sv = cell.conf["serving"]
    del p
    if patch is not None:
        patch(eng)

    arrivals = traffic.schedule(cell.mix, seed, seconds)
    prompts = traffic.prompt_tokens(arrivals, seed, m["vocab_size"])
    lead = float(cell.mix["lead_in_s"])
    end = lead + seconds + float(cell.mix["drain_max_s"])
    counters: Dict[str, Dict] = {}
    traced: Dict = {}

    async def during(drive):
        import asyncio
        await asyncio.sleep(max(0.0, drive.w0 - time.perf_counter()))
        counters["w0"] = dataclasses.asdict(eng.stats)
        watch.log(True)
        n_comp = len(watch.times)
        if trace:
            tmp = tempfile.mkdtemp(prefix="bench-trace-")
            traced["dir"] = tmp
            a = time.perf_counter()
            jax.profiler.start_trace(tmp)
            traced["stalls"] = [(a, time.perf_counter())]
            await asyncio.sleep(TRACE_SETTLE_S)
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
            traced["perf"] = (time.perf_counter(),
                              time.perf_counter() + TRACE_S)
            await asyncio.sleep(TRACE_S)
            traced["perf"] = (traced["perf"][0], time.perf_counter())
            span.__exit__(None, None, None)
            a = time.perf_counter()
            jax.profiler.stop_trace()
            traced["stalls"].append((a, time.perf_counter()))
        await asyncio.sleep(max(0.0, drive.w1 - time.perf_counter()))
        counters["w1"] = dataclasses.asdict(eng.stats)
        counters["compiles"] = len(watch.times) - n_comp
        watch.log(False)

    drive = serve.Drive(eng, sv["planner"], cfg.name, trace)
    drive.run(arrivals, prompts, lead, lead + seconds, end, during)
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    by_idx = {a.idx: a for a in arrivals}
    rec = drive.records
    limits = cell.mix["limits"]
    e2e = stats.end_to_end(list(rec.values()), drive.w0, drive.w1,
                           drive.drain_end, limits["ttft_ms"] / 1e3,
                           limits["tbt_ms"] / 1e3)
    e2e["setup_s"] = drive.t0 + arrivals[0].due - T_PROCESS

    result: Dict = {"correct": False, "attempted": e2e["attempted"],
                    "failed": e2e["failed"], "metrics": {}}
    device = {**info, "memory_peak_bytes": int(mem)}
    ctx = None
    if trace:
        from harness import trace as tr
        ctx = Context(m, family, peaks, drive, by_idx, counters,
                      not eng.chunk_capable())
        ctx.trace = tr.reduce_trace(traced["dir"])
        ctx.trace_perf = (traced["perf"][0], ctx.trace["window"][0])
        ctx.stalls = traced["stalls"]
        if dump:
            Path(dump).mkdir(parents=True, exist_ok=True)
            with open(Path(dump) / f"{workload}.describe.json", "w") as f:
                json.dump(tr.describe(traced["dir"]), f)
            tr.save(ctx.trace, str(Path(dump) / f"{workload}.trace.json.gz"))
        shutil.rmtree(traced["dir"], ignore_errors=True)
        for k in (BENCH / "kernels").glob("*.py"):
            if k.stem != "__init__":
                ctx.kernels[k.stem] = load_module(k)
        for entry in cell.per_layer:
            reader = load_module(reader_file(entry["name"]))
            value = reader.read(ctx)
            if value is not None:
                result["metrics"][entry["name"]] = {"value": value,
                                                    "unit": entry["unit"]}
        t0, t1 = ctx.trace["window"]
        ops = [tr.first_device(ctx.trace)["ops"]] if ctx.trace["devices"] \
            else []
        busy = [tr.busy_ns(o, t0, t1) for o in ops]
        device["busy_s"] = (sum(busy) / len(busy) / 1e9) if busy else 0.0
        device["window_s"] = (t1 - t0) / 1e9
        if ops:
            result["breakdown"] = {
                "device_ops": [[tr.short(n), d / 1e9] for n, d in
                               tr.op_totals(ops[0], t0, t1)[:10]],
                "idle_gaps": [[n, d / 1e9] for n, d in
                              tr.idle_gaps(ops[0], ctx.trace["spans"], t0,
                                           t1)[:10]]}
    else:
        for entry in cell.e2e:
            v = e2e[entry["name"].split(".")[0]]
            result["metrics"][entry["name"]] = {"value": v,
                                                "unit": entry["unit"]}
    result["device"] = device

    # ---- correctness: a seeded sample of the finished requests against
    # the plain reference, once the program's state is gone
    done = [rid for rid, r in rec.items() if r.done]
    seeds = drive.seed_tokens(done)
    served = {rid: list(drive.planner.streams[rid]) for rid in done}
    compiles_in_window = counters.get("compiles", 0)
    compiled_names = list(watch.names)
    drive.close()
    ctx = drive = eng = None
    gc.collect()
    from harness import check
    checks, n_checked, ctl = check.compare(
        family, m, weights, cell.conf, by_idx, prompts, done, seeds, served,
        seed, control)
    result["correct"] = check.passed(checks)
    if control:
        result["control"] = check.control_verdicts(checks, ctl or {})
        for mode, v in result["control"].items():
            print(f"control {mode}: logit_gap {v['logit_gap'][0]} (limit "
                  f"{v['logit_gap'][1]}), correct {v['correct']}",
                  file=sys.stderr)
    result["checks"] = checks
    if compiles_in_window:
        print(f"bench: {compiles_in_window} compiles inside the window: "
              + "; ".join(compiled_names[:10]), file=sys.stderr)
    print(f"bench: warm-up {warm}, setup {e2e['setup_s']:.3f} s, "
          f"{e2e['attempted']} requests in the window, ttft samples "
          f"{e2e['ttft_samples']} (p95 {e2e['ttft_p95_ms']:.1f} ms), tbt "
          f"samples {e2e['tbt_samples']}, "
          f"{n_checked} served tokens checked",
          file=sys.stderr)
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None,
                    help="directory for the raw trace's description and "
                         "the reduced trace (traced runs)")
    ap.add_argument("--control", action="store_true",
                    help="also judge the int8 and fp8 controls on the "
                         "same sample (each must read not correct)")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rate multiples: serve each in "
                         "turn and print end-to-end numbers (no result)")
    a = ap.parse_args(argv)
    if a.sweep:
        sweep(a.workload, a.seed, a.seconds,
              [float(x) for x in a.sweep.split(",")])
        return 0
    res = run(a.workload, a.seed, a.seconds, bool(a.trace),
              dump=a.dump, control=a.control)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
