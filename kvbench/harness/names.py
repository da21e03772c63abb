"""Names to files.

``BENCHMARK.json`` names every workload, configuration, traffic mix and
metric; each name is one file under ``kvbench/``. A configuration's file may
name two more: its plain reference and its counts. A later PR adds a file
and an entry, and edits nothing here. A name with no file fails with the
path that was looked for.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

KVBENCH = Path(__file__).resolve().parents[1]
ROOT = KVBENCH.parent


class MissingFile(FileNotFoundError):
    """A name in ``BENCHMARK.json`` (or in a data file) has no file."""


def _need(path: Path, what: str) -> Path:
    if not path.is_file():
        raise MissingFile(f"{what}: no file {path}")
    return path


def load_json(path: Path, what: str) -> dict:
    with open(_need(path, what), encoding="utf-8") as fh:
        return json.load(fh)


def load_module(path: Path, what: str):
    """Import one file by path: metric and generator names may hold dots
    and dashes, which a package import cannot."""
    _need(path, what)
    tag = "kvbench_file_" + "".join(c if c.isalnum() else "_"
                                    for c in str(path.relative_to(KVBENCH)))
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json", "the benchmark's contract")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"workload {name!r} is not in BENCHMARK.json "
                   f"(it has: {known})")


def config_file(bench: dict, name: str, root: Path = ROOT) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return root / c["file"]
    raise KeyError(f"configuration {name!r} is not in BENCHMARK.json")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    return load_json(config_file(bench, name, root),
                     f"configuration {name!r}")


def config_for_run(bench: dict, name: str, rehearse: bool,
                   root: Path = ROOT) -> dict:
    """The configuration as it is run. Its ``kvbench.rehearse`` group holds
    toy sizes for the CPU walk-through: ``model`` replaces published keys,
    ``engine`` and ``probe`` replace keys of those groups."""
    return as_run(config(bench, name, root), rehearse)


def as_run(conf: dict, rehearse: bool) -> dict:
    """A configuration file's content as it is run (see above)."""
    kv = dict(conf["kvbench"])
    toy = kv.pop("rehearse", {})
    if rehearse:
        conf = {**conf, **toy.get("model", {})}
        for group in ("engine", "probe"):
            kv[group] = {**kv.get(group, {}), **toy.get(group, {})}
    conf["kvbench"] = kv
    return conf


def traffic(name: str) -> dict:
    return load_json(KVBENCH / "traffic" / f"{name}.json",
                     f"traffic mix {name!r}")


def generator(name: str):
    mod = load_module(KVBENCH / "generators" / f"{name}.py",
                      f"traffic generator {name!r}")
    if not callable(getattr(mod, "schedule", None)):
        raise AttributeError(f"{mod.__file__} has no schedule()")
    return mod


def metric(name: str):
    mod = load_module(KVBENCH / "metrics" / f"{name}.py",
                      f"metric {name!r}")
    for attr in ("NAME", "UNIT", "SOURCE", "compute"):
        if not hasattr(mod, attr):
            raise AttributeError(f"{mod.__file__} has no {attr}")
    if mod.NAME != name:
        raise ValueError(f"{mod.__file__} calls itself {mod.NAME!r}")
    return mod


def _configuration_module(conf: dict, key: str, default: str, needs):
    """The module a configuration's ``kvbench`` group names under ``key``:
    a file under ``kvbench/`` (by convention ``references/<config>.py``,
    ``counts/<config>.py``), or ``default`` where it names none."""
    rel = conf["kvbench"].get(key) or default
    path = (KVBENCH / rel).resolve()
    if KVBENCH not in path.parents:
        raise ValueError(f"a configuration's {key} is a file under "
                         f"{KVBENCH}, not {rel!r}")
    mod = load_module(path, f"{key} {rel!r} of the configuration for "
                            f"{conf['kvbench'].get('model_name')!r}")
    for attr in needs:
        if not hasattr(mod, attr):
            raise AttributeError(f"{mod.__file__} has no {attr}")
    return mod


def reference(conf: dict):
    """The plain forward this configuration's model is checked against:
    ``logits_at(params, cfg, tokens, positions)`` and ``TOLERANCE``."""
    return _configuration_module(conf, "reference", "reference.py",
                                 ("logits_at", "TOLERANCE"))


def counts(conf: dict):
    """What this configuration's model needs, from shapes alone:
    ``prefill_flops(cfg, pos, n)`` and ``decode_attention_bytes(cfg,
    keys)``, which the roofline readers divide by."""
    return _configuration_module(conf, "counts", "trace/opcount.py",
                                 ("prefill_flops", "decode_attention_bytes"))


def cell_metrics(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metric entries this cell reports in this mode: its end-to-end
    metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``.
    An entry without ``workloads`` is in every cell."""
    entries = bench["per_layer" if traced else "end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def with_rehearsal(doc: dict, rehearse: bool) -> dict:
    """A data file may carry a ``rehearse`` group: toy values that replace
    the real ones (one level deep, groups merged) for the CPU walk-through.
    The group itself is dropped either way."""
    out = {k: v for k, v in doc.items() if k != "rehearse"}
    if rehearse:
        for key, val in doc.get("rehearse", {}).items():
            if isinstance(val, dict) and isinstance(out.get(key), dict):
                out[key] = {**out[key], **val}
            else:
                out[key] = val
    return out
