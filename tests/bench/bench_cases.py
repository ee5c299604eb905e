"""Shared set-up of the benchmark's tests: the repository root on the
path (for `import bench`), and the cells cut to a size the CPU runs in
seconds, through the same driver functions the chip runs. (A module of
its own and not a conftest.py: the tests above import their conftest by
name.)"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, spec  # noqa: E402

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def small_train_cell(phase):
    c = spec.cell(f"spion-lra.listops-{phase}")
    c.config = dict(c.config, num_hidden_layers=2,
                    spion=dict(c.config["spion"], block_size=16))
    c.traffic = dict(c.traffic, batch=4, seq_len=128, pool=4,
                     reference_chunk=2, trace_steps=1)
    return c


def small_serve_cell():
    c = spec.cell("qwen2-7b-16l.chat-sparse")
    c.config = dict(c.config, hidden_size=64, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, intermediate_size=128,
                    num_hidden_layers=2, vocab_size=256,
                    spion=dict(c.config["spion"], block_size=16),
                    serving=dict(slots=4, max_len=160, prefill_bucket=32))
    c.traffic = dict(c.traffic, rate_per_s=4.0,
                     prompt=dict(c.traffic["prompt"], median=60, min=20,
                                 max=120),
                     output=dict(c.traffic["output"], median=8, min=4, max=24),
                     drain_s=60, trace_seconds=0.5, sample_tokens=20,
                     sample_max=4)
    return c


def drive(cell, *, seed=2**31 + 11, seconds=1.0, **kw):
    """One run of a cell's driver; returns (Run, result line)."""
    run = harness.Run(cell=cell, peaks=PEAKS)
    spec.driver(cell.traffic["kind"]).run(
        run, seed=seed, seconds=seconds, trace=False, t_process=0.0,
        compiles=harness.CompileCounter(), **kw)
    return run, harness.result(run, False)


def run_on_cpu_devices(code, devices=4):
    """`code` in a fresh interpreter with `devices` virtual CPU devices (JAX
    fixes the count when it starts); returns its standard output."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = {"PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "src"),
                                          here]),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "JAX_PLATFORMS": "cpu", "PATH": os.environ.get("PATH", "")}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout
