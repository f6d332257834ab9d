"""The benchmark's core: find a cell's files by name, check the device,
time set-up, and print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

  bench/configs/<config>.json    the configuration as it is run
  bench/adapters/<config>.py     how the program is built for it, and how
                                 the benchmark's weights map onto the
                                 program's parameter tree
  bench/refs/<config>.py         the plain float32 reference
  bench/traffic/<traffic>.json   the traffic mix; its ``kind`` names
  bench/kinds/<kind>.py          the generic driver of that kind
  bench/metrics/<metric>.py      one per-layer metric's reader

This module never imports JAX at module level: ``run.py`` checks the
device first.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import pathlib
import sys
from typing import Any

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SetupError(RuntimeError):
    """The run cannot be made: no device, wrong chip count, a missing file."""


def load_module(path: pathlib.Path):
    """Import a file by path.  A file of this package whose name is an
    identifier is imported under its dotted name, so that it is one module
    with what imports it by name; other names (with '-' or '.') load under
    a name made from the path."""
    if not path.is_file():
        raise SetupError(f"missing {path}")
    rel = path.resolve().relative_to(ROOT) if path.resolve().is_relative_to(ROOT) else None
    if rel is not None and all(p.isidentifier() for p in rel.with_suffix("").parts):
        return importlib.import_module(".".join(rel.with_suffix("").parts))
    mod_name = "bench_file_" + "".join(c if c.isalnum() else "_" for c in str(path))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SetupError(f"missing {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names loaded."""
    name: str
    chips: int
    config: dict                 # bench/configs/<config>.json
    traffic: dict                # bench/traffic/<traffic>.json
    end_to_end: list[dict]       # the e2e metric entries this cell reports
    per_layer: list[dict]        # the per-layer metric entries it reports
    bench_dir: pathlib.Path = BENCH_DIR

    @property
    def config_name(self) -> str:
        return self.config["name"]

    def _module(self, group: str, name: str):
        return load_module(self.bench_dir / group / f"{name}.py")

    def adapter(self):
        return self._module("adapters", self.config_name)

    def ref(self):
        return self._module("refs", self.config_name)

    def kind(self):
        return self._module("kinds", self.traffic["kind"])

    def metric_reader(self, name: str):
        return self._module("metrics", name)


def _applies(metric: dict, cell: str, reported: set[str] | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if reported is None:            # an end-to-end metric with no list
        return True
    return metric["moves"] in reported


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The workload ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / bench["paths"][0]
    config = read_json(root / cfgs[w["config"]]["file"])
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, None)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer,
                bench_dir)


def check_devices(chips: int):
    """The cell's devices, or SetupError: no accelerator, or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SetupError(
            f"needs a TPU; JAX found {len(devices)} {devices[0].platform} "
            f"device(s)")
    if len(devices) < chips:
        raise SetupError(f"needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache`` (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept, so only
    the first run of a cell in a checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_record(devices) -> dict:
    """``device`` of the result line: as JAX reports it, and the peak bytes
    on the fullest chip."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def result_line(*, checks: list[Check], attempted: int, failed: int,
                metrics: dict[str, tuple[float, str]], device: dict,
                breakdown: dict | None = None) -> dict:
    correct = bool(checks) and all(c.ok for c in checks) and failed == 0
    out: dict[str, Any] = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": {k: {"value": v, "unit": u} for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def print_checks(checks: list[Check]) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
