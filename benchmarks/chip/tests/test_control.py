"""The check's control: the reference, one precision below the one the
configuration states, put in the program's place, must come out as not
correct.  ``calibrate.py`` reads it on the chip at each cell's own size;
here it runs at test sizes.  On the CPU every matmul precision computes in
float32, so the BO-GP control (matmuls at "high") can only be read on a
TPU; the TPE control (bfloat16) reads the same on both."""

import jax
import pytest

from conftest import DATA


@pytest.mark.parametrize("workload", ["tiny.reuse", "tiny.tpe"])
def test_control_is_not_correct(tiny_root, workload):
    import harness
    if workload == "tiny.reuse" and jax.default_backend() != "tpu":
        pytest.skip("matmul precision does not change float32 on the CPU")
    c = harness.Cell(workload, jax.devices()[:1], root=tiny_root,
                     bench_dir=DATA)
    try:
        c.setup(2 ** 32 + 17)
        c.run_window(2.0)
        program = c.numbers()
        control = c.numbers(control=True)
    finally:
        c.close()
    assert harness.judge(program, c.limits)[0], program
    compared = {k: v for k, v in control.items() if k in c.limits}
    ok, rows = harness.judge(dict(program, **compared), c.limits)
    assert not ok, rows
