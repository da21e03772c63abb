"""``check_line`` accepts a good line of each mode and refuses each fault
the issue names."""

import copy
import math

import pytest

from kvbench.harness.check_line import BadLine, check_line, faults

E2E = [{"name": "ttft_p50_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]
LAYER = [{"name": "device_idle_share", "unit": "%"},
         {"name": "evictions_per_s", "unit": "1/s"}]


def good(traced: bool) -> dict:
    line = {
        "correct": True, "attempted": 40, "failed": 0,
        "metrics": ({"device_idle_share": {"value": 41.5, "unit": "%"},
                     "evictions_per_s": {"value": 0.0, "unit": "1/s"}}
                    if traced else
                    {"ttft_p50_ms": {"value": 212.4, "unit": "ms"},
                     "setup_s": {"value": 95.3, "unit": "s"}}),
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 13958643712},
    }
    if traced:
        line["device"].update(busy_s=2.5, window_s=5.0)
        line["breakdown"] = {"device_ops": [["fusion.1", 1.25]],
                             "idle_gaps": [["step", 0.5]]}
    return line


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_passes(traced):
    text = check_line(good(traced), LAYER if traced else E2E, traced)
    assert text.startswith("{") and "\n" not in text


def _set(path, value):
    def change(line):
        at = line
        for key in path[:-1]:
            at = at[key]
        if value is KeyError:
            del at[path[-1]]
        else:
            at[path[-1]] = value
    return change


FAULTS = [
    # (traced, change, a word of the complaint)
    (True, _set(("device", "busy_s"), 5.5), "busy_s"),          # sum of lines
    (True, _set(("device", "busy_s"), 0.0), "busy_s"),          # empty slice
    (True, _set(("device", "busy_s"), KeyError), "busy_s"),
    (True, _set(("device", "window_s"), float("nan")), "window_s"),
    (True, _set(("metrics", "evictions_per_s"), KeyError), "evictions_per_s"),
    (True, _set(("metrics", "device_idle_share", "value"), None),
     "device_idle_share"),
    (True, _set(("metrics", "device_idle_share", "value"), float("nan")),
     "device_idle_share"),
    (True, _set(("metrics", "device_idle_share", "value"), math.inf),
     "device_idle_share"),
    (True, _set(("metrics", "device_idle_share", "unit"), "percent"), "unit"),
    (True, _set(("metrics", "ttft_p50_ms"), {"value": 1.0, "unit": "ms"}),
     "ttft_p50_ms"),                                  # the other mode's metric
    (True, _set(("breakdown", "device_ops"), [["x", float("nan")]]),
     "breakdown"),
    (True, _set(("breakdown", "idle_gaps"), [["g", 0.1]] * 11), "breakdown"),
    (False, _set(("breakdown",), {"device_ops": [], "idle_gaps": []}),
     "breakdown"),                                    # untraced run
    (False, _set(("extra",), 1), "extra"),
    (False, _set(("correct",), KeyError), "correct"),
    (False, _set(("correct",), "yes"), "correct"),
    (False, _set(("attempted",), -1), "attempted"),
    (False, _set(("failed",), 41), "failed"),
    (False, _set(("device", "memory_peak_bytes"), KeyError),
     "memory_peak_bytes"),
    (False, _set(("device", "count"), 0), "count"),
    (False, _set(("device", "platform"), ""), "platform"),
    (False, _set(("metrics", "setup_s", "value"), "95"), "setup_s"),
    (False, _set(("metrics", "setup_s", "value"), True), "setup_s"),
]


@pytest.mark.parametrize("traced,change,word", FAULTS)
def test_each_fault_is_refused(traced, change, word):
    line = copy.deepcopy(good(traced))
    change(line)
    expected = LAYER if traced else E2E
    found = faults(line, expected, traced)
    assert found and any(word in f for f in found), found
    with pytest.raises(BadLine):
        check_line(line, expected, traced)


@pytest.mark.parametrize("traced", [False, True])
def test_compared_is_optional_last_and_pairs_of_numbers(traced):
    """The numbers that decided ``correct`` beside their limits: a key of
    its own that comes last; the driver ignores it."""
    expected = LAYER if traced else E2E
    line = good(traced)
    line["compared"] = {"prefill_err": [0.021, 0.05], "probe_faults": [0, 0]}
    assert '"compared"' in check_line(line, expected, traced)
    first = {"compared": line["compared"], **good(traced)}
    assert "last key" in "; ".join(faults(first, expected, traced))
    for wrong in ({"prefill_err": 0.021}, {"prefill_err": [0.021]},
                  {"prefill_err": [math.nan, 0.05]}, [0.021, 0.05]):
        line["compared"] = wrong
        assert "name: [number, limit]" in "; ".join(
            faults(line, expected, traced))
