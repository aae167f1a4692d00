"""Find a cell and the files that belong to it, by name.

Everything cell-specific is data: ``BENCHMARK.json`` names the cell's
configuration file, its traffic mix and its metrics; the mix is
``traffic/<name>.json`` and each metric is ``metrics/<name>.py``, looked up
in ``bench/`` and then in any extra data directories given (tests add their
own files that way, without touching these)."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Iterable, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(path: Optional[Path] = None) -> dict:
    return json.loads(Path(path or ROOT / "BENCHMARK.json").read_text())


def find_cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have: {', '.join(w['name'] for w in bm['workloads'])})")


def load_config(bm: dict, name: str, root: Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            cfg = json.loads((root / c["file"]).read_text())
            cfg.setdefault("name", name)
            return cfg
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def _find(sub: str, fname: str, dirs: Iterable[Path]) -> Path:
    for d in [BENCH, *map(Path, dirs)]:
        p = d / sub / fname
        if p.exists():
            return p
    raise FileNotFoundError(f"{sub}/{fname} not found under bench/ or "
                            f"{[str(d) for d in dirs]}")


def load_traffic(name: str, dirs: Iterable[Path] = ()) -> dict:
    mix = json.loads(_find("traffic", f"{name}.json", dirs).read_text())
    mix.setdefault("name", name)
    return mix


def load_metric(name: str, dirs: Iterable[Path] = ()):
    """The metric's reader module: a ``read(run) -> float | None``."""
    path = _find("metrics", f"{name}.py", dirs)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metrics_for(bm: dict, cell: str, section: str) -> list:
    """Entries of ``section`` ('end_to_end' | 'per_layer') this cell reports:
    those without a ``workloads`` key, and those that list the cell."""
    return [m for m in bm[section]
            if "workloads" not in m or cell in m["workloads"]]
