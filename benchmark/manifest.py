"""`BENCHMARK.json` and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the NAME in the manifest:

    benchmark/configs/<config>.json     (the manifest gives the path)
    benchmark/traffic/<traffic>.json
    benchmark/metrics/<metric>.json     (every metric: its reader, what it
                                         reads and under which loops)

so a later PR adds a cell, a configuration, a mix or a counter-backed
metric by adding files and manifest entries, and edits nothing.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(Exception):
    """The manifest or a file it names is missing or malformed."""


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from None


@dataclass
class Cell:
    """One entry of `workloads` with the files it names, loaded."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, self.doc["paths"][0])

    # ---- metrics --------------------------------------------------------

    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", (cell,))]

    def per_layer(self, cell: str) -> List[dict]:
        """A metric with a `workloads` list is for those cells; without
        one, for every cell that reports the end-to-end metric it moves.
        A metric's own file may narrow that by what the harness can see
        of a cell: `loops` names the traffic loops under which its source
        takes an observation (the scheduler's lane sees no `_msearch`
        batch of a closed loop), so a cell that comes later as data gets
        the set that has something to read there, with no list to edit."""
        reported = {m["name"] for m in self.end_to_end(cell)}
        loop = self.cell(cell).traffic.get("loop", "open")
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)
                and loop in self.metric_spec(m["name"]).get("loops", (loop,))]

    def declared(self, cell: str, trace: int) -> List[dict]:
        """The metrics a run of `cell` has to print: the cell's end-to-end
        metrics with --trace 0, its per-layer metrics with --trace 1."""
        return self.per_layer(cell) if trace else self.end_to_end(cell)

    def metric_spec(self, name: str) -> dict:
        """benchmark/metrics/<name>.json: the reader's kind and what it
        reads."""
        return _load(os.path.join(self.dir, "metrics", name + ".json"))

    # ---- cells ----------------------------------------------------------

    def cell_names(self) -> List[str]:
        return [w["name"] for w in self.doc["workloads"]]

    def cell(self, name: str) -> Cell:
        w = next((w for w in self.doc["workloads"] if w["name"] == name),
                 None)
        if w is None:
            raise ManifestError(
                f"no workload {name!r}; BENCHMARK.json has "
                f"{self.cell_names()}")
        c = next((c for c in self.doc["configs"] if c["name"] == w["config"]),
                 None)
        if c is None:
            raise ManifestError(f"workload {name!r}: no config "
                                f"{w['config']!r}")
        return Cell(
            name=name, chips=int(w["chips"]), config_name=w["config"],
            traffic_name=w["traffic"],
            config=_load(os.path.join(self.root, c["file"])),
            traffic=_load(os.path.join(self.dir, "traffic",
                                       w["traffic"] + ".json")))


def name_faults(doc: dict) -> List[str]:
    """Every name and unit of a manifest that breaks the character rules
    (letters a-z A-Z, digits, `_ . -`; a unit may add `/ %`)."""
    bad: List[str] = []
    names: Dict[str, Optional[str]] = {}
    for c in doc.get("configs", ()):
        names[f"config {c['name']}"] = c["name"]
        for k in c.get("reduced", ()):
            names[f"reduced key {k} of {c['name']}"] = k
    for w in doc.get("workloads", ()):
        for key in ("name", "config", "traffic"):
            names[f"workload {key} {w[key]}"] = w[key]
    for m in list(doc.get("end_to_end", ())) + list(doc.get("per_layer", ())):
        names[f"metric {m['name']}"] = m["name"]
        if not UNIT_RE.match(m.get("unit", "")):
            bad.append(f"unit {m.get('unit')!r} of {m['name']}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"better {m.get('better')!r} of {m['name']}")
    bad += [what for what, n in names.items() if not NAME_RE.match(n or "")]
    return bad
