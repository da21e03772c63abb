"""The last line, checked before it is printed.

One function holds the shape the driver reads: exactly the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` only in
a traced run), and last ``compared``, which the driver does not read: every
number that decided ``correct`` beside its limit; every metric the cell
lists for the mode, each a finite number with its unit; the device as JAX reports it, and in a traced run
``0 < busy_s <= window_s``. ``run.py`` calls it on its own line; a faulty
line is never printed.
"""

from __future__ import annotations

import json
import math

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_DEVICE_KEYS = ("busy_s", "window_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
BREAKDOWN_MAX = 10
# Optional, and the line's last key: name -> [number, limit].
COMPARED = "compared"


class BadLine(ValueError):
    """The line would be refused; the message says every fault found."""


def _is_number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def faults(line: dict, expected: list[dict], traced: bool) -> list[str]:
    """Everything wrong with ``line`` for a cell whose metrics of this mode
    are ``expected`` (entries of BENCHMARK.json: ``name`` and ``unit``)."""
    bad: list[str] = []
    if not isinstance(line, dict):
        return [f"the line is a {type(line).__name__}, not an object"]
    allowed = set(KEYS) | {COMPARED} | ({"breakdown"} if traced else set())
    for key in KEYS:
        if key not in line:
            bad.append(f"key {key!r} is missing")
    for key in line:
        if key not in allowed:
            bad.append(f"key {key!r} does not belong on the line"
                       + ("" if traced or key != "breakdown"
                          else " of an untraced run"))
    if bad:
        return bad

    if not isinstance(line["correct"], bool):
        bad.append("correct is not true or false")
    for key in ("attempted", "failed"):
        v = line[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            bad.append(f"{key} is not a count: {v!r}")
    if not bad and line["failed"] > line["attempted"]:
        bad.append("failed exceeds attempted")

    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        bad.append("metrics is not an object")
        metrics = {}
    want = {m["name"]: m["unit"] for m in expected}
    for name, unit in want.items():
        got = metrics.get(name)
        if got is None:
            bad.append(f"metric {name!r} is missing")
            continue
        if not isinstance(got, dict) or set(got) != {"value", "unit"}:
            bad.append(f"metric {name!r} is not {{value, unit}}: {got!r}")
            continue
        if not _is_number(got["value"]):
            bad.append(f"metric {name!r} has no finite number: "
                       f"{got['value']!r}")
        if got["unit"] != unit:
            bad.append(f"metric {name!r} has unit {got['unit']!r}, the "
                       f"contract says {unit!r}")
    for name in metrics:
        if name not in want:
            bad.append(f"metric {name!r} is not one this cell reports "
                       f"with --trace {int(traced)}")

    dev = line["device"]
    if not isinstance(dev, dict):
        return bad + ["device is not an object"]
    need = DEVICE_KEYS + (TRACE_DEVICE_KEYS if traced else ())
    for key in need:
        if key not in dev:
            bad.append(f"device.{key} is missing")
    for key in ("platform", "kind"):
        if key in dev and (not isinstance(dev[key], str) or not dev[key]):
            bad.append(f"device.{key} is not a name: {dev[key]!r}")
    for key in ("count", "memory_peak_bytes"):
        if key in dev and (not isinstance(dev[key], int)
                           or isinstance(dev[key], bool) or dev[key] < 0):
            bad.append(f"device.{key} is not a count: {dev[key]!r}")
    if dev.get("count") == 0:
        bad.append("device.count is 0")
    if traced and all(k in dev for k in TRACE_DEVICE_KEYS):
        busy, window = dev["busy_s"], dev["window_s"]
        if not _is_number(busy) or not _is_number(window):
            bad.append(f"device.busy_s / window_s are not finite numbers: "
                       f"{busy!r} / {window!r}")
        elif not 0 < busy <= window:
            bad.append(f"device.busy_s {busy!r} is not above 0 and at most "
                       f"window_s {window!r}")

    if COMPARED in line:
        rows = line[COMPARED]
        if list(line)[-1] != COMPARED:
            bad.append("compared is not the line's last key")
        if not isinstance(rows, dict) or not all(
                isinstance(r, list) and len(r) == 2
                and all(_is_number(x) for x in r) for r in rows.values()):
            bad.append("compared is not an object of name: [number, limit]")

    if "breakdown" in line:
        bd = line["breakdown"]
        if not isinstance(bd, dict) or set(bd) - set(BREAKDOWN_KEYS):
            bad.append("breakdown holds other keys than device_ops and "
                       "idle_gaps")
        else:
            for key, rows in bd.items():
                ok = (isinstance(rows, list) and len(rows) <= BREAKDOWN_MAX
                      and all(isinstance(r, list) and len(r) == 2
                              and isinstance(r[0], str) and _is_number(r[1])
                              for r in rows))
                if not ok:
                    bad.append(f"breakdown.{key} is not a list of at most "
                               f"{BREAKDOWN_MAX} [name, seconds] pairs")
    return bad


def check_line(line: dict, expected: list[dict], traced: bool) -> str:
    """The line as the text to print, or :class:`BadLine`."""
    bad = faults(line, expected, traced)
    if bad:
        raise BadLine("; ".join(bad))
    try:
        return json.dumps(line, allow_nan=False)
    except ValueError as exc:  # a NaN hiding in breakdown or elsewhere
        raise BadLine(f"the line does not serialise: {exc}") from exc
