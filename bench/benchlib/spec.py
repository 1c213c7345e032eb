"""Where the benchmark's files are, and loading a cell from them by name.

``BENCHMARK.json`` at the checkout's root names every cell; a cell names
its configuration (``configs/<config>.json``) and its traffic mix
(``traffic/<traffic>.json``). Per-layer metric readers are
``metrics/<metric>.py`` and peaks are ``peaks.json``, all beside this
package. Adding a cell, a mix or a metric adds files and entries; none
of this code changes.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"


class SpecError(RuntimeError):
    """The cell, its files or the device do not fit together."""


def _load(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing benchmark file {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # BENCHMARK.json metric entries for this cell
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    bench_dir = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=_load(bench_dir / "traffic" / f"{w['traffic']}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def load_peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    table = _load(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
