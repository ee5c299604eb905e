"""Finding a cell's parts by name: BENCHMARK.json joins a configuration
(bench/configs/<config>.json), a traffic mix (bench/traffic/<traffic>.json)
and a chip count; the limits of the cell's correctness check are in
bench/limits/<workload>.json. Code is found by name too, as a file of its
own under `bench/` (`part`):

- the configuration's `family` -> bench/families/<family>.py: its sizes
  (`dims`), the plain reference, the map from the program's weight names
  to the reference's, and the model-level work counts;
- the traffic's `kind` -> bench/drivers/<kind>.py: `run(run, *, seed,
  seconds, trace, t_process, compiles)`;
- a training traffic's `task` -> bench/tasks/<task>.py: `batches(seed,
  traffic, *, count, vocab_size)`;
- each per-layer metric -> bench/metrics/<metric>.py: `read(run)`.

So a new family, driver, task or metric is a new file, and no file that
is there needs an edit."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file
    traffic: dict          # the traffic mix file
    limits: dict           # the correctness limits of this cell
    end_to_end: list       # BENCHMARK.json metric entries this cell reports
    per_layer: list

    @property
    def family(self):
        return family(self.config)

    @property
    def model(self) -> dict:
        return self.family.dims(self.config)


def read_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def benchmark():
    return read_json("BENCHMARK.json")


def _covers(metric, workload):
    return workload in metric.get("workloads", [workload])


def cell(workload: str) -> Cell:
    bm = benchmark()
    try:
        w = next(x for x in bm["workloads"] if x["name"] == workload)
    except StopIteration:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json") from None
    c = next(x for x in bm["configs"] if x["name"] == w["config"])
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=read_json(c["file"]),
        traffic=read_json("bench", "traffic", w["traffic"] + ".json"),
        limits=read_json("bench", "limits", workload + ".json"),
        end_to_end=[m for m in bm["end_to_end"] if _covers(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _covers(m, workload)])


_PARTS = {}


def part(kind: str, name: str):
    """The module bench/<kind>/<name>.py under ROOT, loaded once."""
    path = os.path.join(ROOT, "bench", kind, name + ".py")
    if path not in _PARTS:
        if not NAME.match(name) or not os.path.isfile(path):
            raise KeyError(f"no {kind} {name!r} ({path})")
        mod_name = "bench_{}_{}".format(kind, re.sub(r"\W", "_", name))
        s = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(s)
        sys.modules[mod_name] = mod
        s.loader.exec_module(mod)
        _PARTS[path] = mod
    return _PARTS[path]


def family(conf: dict):
    return part("families", conf["family"])


def driver(kind: str):
    return part("drivers", kind)


def task(name: str):
    return part("tasks", name)


def metric_reader(name: str):
    """bench/metrics/<name>.py's `read(run)`."""
    return part("metrics", name).read


def program_config(conf: dict):
    """The program's ModelConfig for this configuration: its registered
    architecture with the file's numbers (the family's `PROGRAM_FIELDS`)
    and SPION settings put in, then checked against the family's reading
    of the file (`program_dims`)."""
    from repro.configs import get_config
    fam = family(conf)
    cfg = get_config(conf["program_arch"])
    cfg = cfg.replace(**{f: conf[k] for k, f in fam.PROGRAM_FIELDS.items()
                         if k in conf})
    cfg = cfg.replace(spion=dataclasses.replace(cfg.spion, **conf["spion"]))
    m = fam.dims(conf)
    got = fam.program_dims(cfg)
    bad = {k: (v, m[k]) for k, v in got.items() if v != m[k]}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"configuration file: {bad}")
    return cfg
