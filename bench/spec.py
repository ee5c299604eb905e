"""Finding a cell's parts by name: BENCHMARK.json joins a configuration
(bench/configs/<config>.json), a traffic mix (bench/traffic/<traffic>.json)
and a chip count; the limits of the cell's correctness check are in
bench/limits/<workload>.json, and each per-layer metric is read by
bench/metrics/<metric>.py."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# published (Hugging Face style) keys -> the program's ModelConfig fields
PROGRAM_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "layer_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype", "qkv_bias": "qkv_bias",
}


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
    def model(self) -> dict:
        return model_dims(self.config)


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


def model_dims(conf: dict) -> dict:
    """The model as the configuration file states it, in the names the
    reference and the work counts use."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "d_model": d, "num_heads": h,
        "num_kv_heads": conf.get("num_key_value_heads", h),
        "head_dim": conf.get("head_dim", d // h),
        "d_ff": conf["intermediate_size"],
        "num_layers": conf["num_hidden_layers"],
        "vocab_size": conf["vocab_size"],
        "gated_mlp": conf["hidden_act"] == "silu",
        "qkv_bias": bool(conf.get("qkv_bias", False)),
        "norm": "rmsnorm" if "rms_norm_eps" in conf else "layernorm",
        "norm_eps": conf.get("rms_norm_eps", conf.get("layer_norm_eps")),
        "rope_theta": conf.get("rope_theta", 0.0),
        "causal": bool(conf["causal"]),
        "dtype": conf["torch_dtype"],
    }


def program_config(conf: dict):
    """The program's ModelConfig for this configuration: its registered
    architecture with the file's numbers and SPION settings put in, then
    checked against the file."""
    from repro.configs import get_config
    cfg = get_config(conf["program_arch"])
    cfg = cfg.replace(**{f: conf[k] for k, f in PROGRAM_FIELDS.items()
                         if k in conf})
    cfg = cfg.replace(spion=dataclasses.replace(cfg.spion, **conf["spion"]))
    m = model_dims(conf)
    got = {"d_model": cfg.d_model, "num_heads": cfg.num_heads,
           "num_kv_heads": cfg.num_kv_heads, "head_dim": cfg.resolved_head_dim,
           "d_ff": cfg.d_ff, "num_layers": cfg.num_layers,
           "vocab_size": cfg.vocab_size, "gated_mlp": cfg.act == "silu",
           "qkv_bias": cfg.qkv_bias, "causal": cfg.causal, "dtype": cfg.dtype}
    bad = {k: (v, m[k]) for k, v in got.items() if v != m[k]}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"configuration file: {bad}")
    return cfg


def metric_reader(name: str):
    """bench/metrics/<name>.py's `read(ctx)`."""
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
