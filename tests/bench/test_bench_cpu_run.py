"""The harness end to end on the CPU at the program's smoke size: it
serves, reads its per-layer metrics, reports no device metric, and its
check passes; with a served token altered where the engine produces it,
the same check fails."""
import math

import pytest

from benchpath import load_run

# small enough that a CPU run warms a few dozen executables
OVERRIDES = {
    "serving": {"n_slots": 3, "cache_len": 64, "page_size": 8,
                "total_pages": 24},
    "mix": {"arrival": {"process": "poisson", "rate_rps": 6.0},
            "prompt_tokens": {"median": 10, "sigma": 0.5, "min": 4,
                              "max": 24},
            "output_tokens": {"median": 5, "sigma": 0.5, "min": 2,
                              "max": 10},
            "lead_in_s": 0.5, "drain_max_s": 5.0},
}
# readers of device metrics (a metric split per configuration, such as
# engine.decode_ms.dense, is read by the reader of its stem)
DEVICE_METRICS = {"engine.decode_ms", "paged_decode_roofline",
                  "ssd_scan_roofline", "step_mfu", "step_mfu.prefill",
                  "device.idle_share.loaded"}
SUFFIX = {"olmo-1b.chat-steady": "dense", "mamba2-1.3b.chat-burst": "ssm"}


def _overrides(run, cell):
    planner = dict(run.load_cell(cell).conf["serving"]["planner"],
                   chunk_tokens=16)
    return {"serving": {**OVERRIDES["serving"], "planner": planner},
            "mix": OVERRIDES["mix"]}


@pytest.mark.parametrize("cell", ["olmo-1b.chat-steady",
                                  "mamba2-1.3b.chat-burst"])
def test_traced_run_on_cpu(cell):
    run = load_run()
    res = run.run(cell, 2 ** 31 + 3, 2.0, True, device_check=False,
                  reduced=True, overrides=_overrides(run, cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"
    assert not DEVICE_METRICS & {run.reader_file(n).stem
                                 for n in res["metrics"]}
    sfx = SUFFIX[cell]
    assert res["metrics"][f"engine.compile_events_in_window.{sfx}"][
        "value"] == 0
    assert f"planner.host_ms_per_tick.{sfx}" in res["metrics"]


def _altered(eng):
    """Every third decode step emits a token one above the one it chose."""
    step = eng.step
    calls = [0]

    def bad_step(slots=None, forced=None):
        tok, done = step(slots, forced)
        calls[0] += 1
        if calls[0] % 3 == 0:
            tok = (tok + 1) % eng.cfg.vocab_size
        return tok, done
    eng.step = bad_step


def _fails_with_altered_token(cell, seed):
    run = load_run()
    res = run.run(cell, seed, 2.0, False, device_check=False, reduced=True,
                  overrides=_overrides(run, cell), patch=_altered)
    assert not res["correct"]
    gap, limit = res["checks"]["logit_gap"]
    assert gap > limit and math.isfinite(gap)


def test_altered_token_fails_the_check():
    _fails_with_altered_token("olmo-1b.chat-steady", 17)


def test_altered_token_fails_the_check_ssm():
    _fails_with_altered_token("mamba2-1.3b.chat-burst", 19)
