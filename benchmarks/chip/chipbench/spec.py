"""Discovery: everything that belongs to one configuration, traffic mix,
per-cell check or metric sits in a file of its own, found by the name
``BENCHMARK.json`` gives it.

  configs/<config>.json     sizes, precision, serving mode; names its
                            plain reference
  references/<name>.py      a plain reference (``replay``, ``sizes``)
  systems/<name>.py         the system under test of that reference: the
                            only place the program is imported
  traffic/<traffic>.json    parameters of the one traffic generator
  checks/<cell>.json        the limits of the comparison for one cell
  metrics/<metric>.py       a reader: ``read(ctx) -> float | None``
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "configs", f"{name}.json"))


def traffic(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "traffic", f"{name}.json"))


def limits(cell_name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "checks", f"{cell_name}.json"))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(name: str, here: str = HERE):
    return _module(os.path.join(here, "references", f"{name}.py"),
                   f"chipbench_reference_{name}")


def system(name: str, here: str = HERE):
    """The system under test of the reference ``name``: ``check_config``,
    ``make_weights``, ``reference_weights``, ``make_engine``,
    ``telemetry``, ``frame_ops``, ``head_macs``, ``NUMBERS`` and
    ``compare``."""
    return _module(os.path.join(here, "systems", f"{name}.py"),
                   f"chipbench_system_{name}")


def reader(metric: str, here: str = HERE):
    safe = "".join(c if c.isalnum() else "_" for c in metric)
    return _module(os.path.join(here, "metrics", f"{metric}.py"),
                   f"chipbench_metric_{safe}").read


def metrics_for(bench: dict, cell_name: str, trace: bool) -> list:
    """The metrics a run of ``cell_name`` reports: its end-to-end metrics
    without tracing, its per-layer metrics with. A metric with a
    ``workloads`` list applies to those cells; one without applies to
    every cell that reports the end-to-end metric it moves (or, for an
    end-to-end metric, to every cell)."""
    def applies(m):
        return cell_name in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
