"""The control at a test size: the reference one precision step below
(float8) put in the program's place must come out not correct under the
cell's limits, as ``bench/check.py`` decides ``correct``, while the program
itself comes out correct."""
from bench import control
from bench.tests import tiny


def test_control_reads_above_the_limit_and_the_program_below():
    r = control.readings(tiny.cell(), 2**31 + 101, 2.0, require_tpu=False)
    program, ctl = r["verdicts"]["f32"], r["verdicts"]["fp8"]
    assert program["correct"] is True and ctl["correct"] is False
    assert set(ctl["numbers"]) == set(tiny.LIMITS) - {"thought_mismatch"}
    assert any(v["value"] > v["limit"] for v in ctl["numbers"].values())
    assert all(v["value"] <= v["limit"] for v in program["numbers"].values())
