"""``programs_per_step`` on a synthetic reduced trace."""

from types import SimpleNamespace

import pytest

from kvbench.harness import names
from kvbench.harness.loop import Run
from kvbench.trace import reduce as R


def test_programs_per_step():
    reader = names.metric("programs_per_step")
    trace = SimpleNamespace(
        window=(0.0, 100.0), planes=["/device:TPU:0"],
        modules={"/device:TPU:0": [
            R.Event("jit_forward_prefill_pallas(1)", 5.0, 10.0),
            R.Event("jit_dynamic_slice(2)", 16.0, 1.0),
            R.Event("jit_squeeze(3)", 18.0, 1.0),
            R.Event("jit_forward_decode_pallas(4)", 20.0, 10.0),
            R.Event("jit_dynamic_slice(5)", 31.0, 1.0),
            R.Event("jit__argmax(6)", 33.0, 1.0),
            R.Event("jit_forward_decode_pallas(4)", 60.0, 10.0),
            R.Event("jit_dynamic_slice(5)", 71.0, 1.0),
            R.Event("jit__argmax(6)", 73.0, 1.0),
            R.Event("jit_forward_decode_pallas(4)", 200.0, 10.0)]},
        work=[{"decode_rows": 1}, {"decode_rows": 1}])
    run = Run(seconds=1.0)
    run.trace = trace
    assert reader.compute(run) == pytest.approx(4.5)  # 9 inside, 2 steps


@pytest.mark.parametrize("trace", [
    None,
    SimpleNamespace(window=(0.0, 1.0), planes=[], modules={}, work=[]),
], ids=["untraced", "no-steps"])
def test_nothing_to_read_is_none(trace):
    run = Run(seconds=1.0)
    run.trace = trace
    assert names.metric("programs_per_step").compute(run) is None


def test_the_recorded_fixture_reads_above_one():
    """PR 24's recorded slice: step programs and the small ones between
    them on the modules line, ``step.work`` markers on the host plane."""
    from pathlib import Path

    spans = ["step"]
    path = Path(__file__).with_name("fixture.xplane.pb")
    run = Run(seconds=1.0)
    run.trace = R.reduce(R.load(str(path), spans), 1, spans)
    if not run.trace.work:
        pytest.skip("the fixture holds no step.work marker")
    assert names.metric("programs_per_step").compute(run) > 1.0
