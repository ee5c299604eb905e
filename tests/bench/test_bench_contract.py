"""BENCHMARK.json against the benchmark's contract: names, units, keys,
the files each entry needs, and the peak table."""
import json
import os
import re

import pytest

import bench_cases  # noqa: F401  (puts the repository on the path)
from bench import peaks, spec

BM = spec.benchmark()
NAME, UNIT = spec.NAME, spec.UNIT
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$"
                   r"|head|expan|experts_per_tok|top_k")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def entries():
    for group, keys in KEYS.items():
        for e in BM[group]:
            yield group, e


@pytest.mark.parametrize("group,entry", list(entries()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_entry_keys_and_names(group, entry):
    extra = set(entry) - KEYS[group] - {"workloads"}
    assert not extra and KEYS[group] <= set(entry)
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower",
                                                                 "higher")
    for k in ("why", "layer", "source"):
        if k in entry and group in ("configs", "workloads", "per_layer"):
            v = entry[k]
            assert 1 <= len(v) <= 200 and "\n" not in v and "\t" not in v


def test_top_level_and_sizes():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BM)) < 64 * 1024
    assert 1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    cmd = BM["command"]
    assert len(cmd) <= 32 and all(not w.startswith("/") and ".." not in w
                                  for w in cmd)
    assert any(cmd[1].startswith(p + "/") for p in BM["paths"])
    names = [e["name"] for _, e in entries()]
    assert len(names) == len(set(names))
    # a full check fits its time with 24 cells at this window length
    assert (2 + 14 * 24) * (BM["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_end_to_end_bounds_and_setup():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_is_whole():
    """Each cell's files exist, it reports setup_s, another end-to-end
    metric and a per-layer one, and each per-layer metric has a reader and
    moves an end-to-end metric its cells report."""
    for w in BM["workloads"]:
        c = spec.cell(w["name"])
        assert w["chips"] in (1, 4)
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert m["moves"] in names
            assert os.path.isfile(os.path.join(spec.ROOT, "bench", "metrics",
                                               m["name"] + ".py"))
        assert set(c.limits["limits"])
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configs_state_their_cuts():
    for c in BM["configs"]:
        conf = spec.read_json(c["file"])
        assert conf["source"] == c["source"]
        assert all(NAME.match(k) and not WIDTH.search(k) for k in c["reduced"])
        assert set(c["reduced"]) == set(conf["reduced"])
        spec.program_config(conf)      # the program agrees with the file


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v4")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
