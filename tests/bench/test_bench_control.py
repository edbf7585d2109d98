"""The check's control: the plain reference computed a precision step
below the configuration's (weights and activations rounded to fp8 before
every matmul), put in the program's place and judged by the harness as
the program is. At the program's CPU smoke size the program and the
reference agree to the last token (widest gap 0 on every seed), while the
fp8 control's tokens lie 0.028-0.126 below the reference's best; the
smoke size's own limit, set between those readings, passes the program
and fails the control. (At published widths on the chip the same holds
for the configurations' own limits: PERF.md.)"""
import pytest

from benchpath import load_run
from test_bench_cpu_run import _overrides

# between the program's widest gap (0) and the fp8 control's least (0.028)
# at the smoke size, with more room above the program
SMOKE_LIMIT = 0.01


@pytest.mark.parametrize("cell", ["olmo-1b.chat-steady",
                                  "mamba2-1.3b.chat-burst"])
def test_fp8_control_reads_above_the_program(cell):
    run = load_run()
    ov = _overrides(run, cell)
    # enough served tokens that a precision step shows: about 200
    ov["mix"] = {**ov["mix"], "output_tokens": {
        "median": 24, "sigma": 0.3, "min": 16, "max": 36}}
    ov["serving"] = {**ov["serving"], "n_slots": 4}
    ov["check"] = {"max_logit_gap": SMOKE_LIMIT}
    for seed in (1, 2, 3):
        res = run.run(cell, seed, 3.0, False, device_check=False,
                      reduced=True, overrides=ov, control=True)
        assert res["correct"], (seed, res["checks"])
        fp8 = res["control"]["fp8"]
        assert fp8["logit_gap"][1] == SMOKE_LIMIT
        assert not fp8["correct"], (seed, fp8)
        assert list(res)[-1] == "checks"
