"""The harness end to end on the CPU, at small widths: every cell of
BENCHMARK.json, its files found by name, a driver run for a second with the
device check steered from here, and the contract's last line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import harness
import smoke

HERE = Path(__file__).resolve().parent
BENCH = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_every_name_has_its_files():
    for c in BENCH["configs"]:
        assert (harness.CHECKOUT / c["file"]).is_file()
        assert harness.load_json(harness.CHECKOUT / c["file"])["name"] == c["name"]
    for w in BENCH["workloads"]:
        cell = harness.cell(w["name"])
        assert harness.load_module("drivers", cell.traffic["driver"]).SMALL
        assert harness.load_module("reference", cell.config["reference"]).SMALL
        assert set(cell.limits) and all("limit" in v for v in cell.limits.values())
        assert cell.end_to_end and cell.per_layer
    for m in BENCH["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


def config_file(name):
    return harness.load_json(HERE / "configs" / f"{name}.json")


def test_program_config_of_the_published_files():
    """Field for field the ModelConfig these two files gave before they
    could state a layout or program fields: the arch's first group repeated
    to num_hidden_layers, and the mapped published sizes."""
    from repro.configs.base import LayerGroup, ModelConfig

    assert harness.program_config(config_file("olmo-1b")) == ModelConfig(
        name="olmo-1b", family="dense", d_model=2048, n_heads=16, n_kv_heads=16, d_ff=8192,
        vocab_size=50304, groups=(LayerGroup(pattern=("attn",), count=16),), head_dim=128,
        n_experts=0, top_k=0, capacity_factor=1.25, window=0, attn_softcap=0.0,
        logit_softcap=0.0, rope_theta=10000.0, pos="rope", norm="nonparam_ln", act="silu",
        gated=True, post_norms=False, tie_embeddings=True, encoder_layers=0, frontend_tokens=0,
        frontend_dim=0, rwkv_head_dim=64, wkv_chunk=32, lru_width=0, conv_width=4,
        dtype="bfloat16")
    assert harness.program_config(config_file("rwkv6-1.6b")) == ModelConfig(
        name="rwkv6-1.6b", family="ssm", d_model=2048, n_heads=32, n_kv_heads=32, d_ff=7168,
        vocab_size=65536, groups=(LayerGroup(pattern=("rwkv",), count=24),), head_dim=None,
        n_experts=0, top_k=0, capacity_factor=1.25, window=0, attn_softcap=0.0,
        logit_softcap=0.0, rope_theta=10000.0, pos="none", norm="layernorm", act="silu",
        gated=True, post_norms=False, tie_embeddings=False, encoder_layers=0, frontend_tokens=0,
        frontend_dim=0, rwkv_head_dim=64, wkv_chunk=32, lru_width=0, conv_width=4,
        dtype="bfloat16")


@pytest.mark.parametrize("extra,message", [
    ({"program": {"n_expert": 8}}, "no fields of ModelConfig"),
    ({"program": {"groups": []}}, "no fields of ModelConfig"),
    ({"program": {"d_model": 1024}}, "disagrees with the published keys on ['d_model']"),
    ({"program": {"d_ff": 4096}}, "disagrees with the published keys on ['d_ff']"),
    ({"groups": [{"pattern": ["attn"], "count": 15}]}, "hold 15 layers, num_hidden_layers is 16"),
])
def test_program_config_refuses_what_it_cannot_run(extra, message):
    with pytest.raises(ValueError) as e:
        harness.program_config(dict(config_file("olmo-1b"), **extra))
    assert message in str(e.value)


def test_without_a_tpu_no_result(capsys):
    import run

    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def run_cell(name, monkeypatch, capsys, seconds="1"):
    import run

    smoke.use(harness, monkeypatch)
    devices = jax.devices()[: harness.cell(name).chips]
    assert run.main(["--workload", name, "--seed", str(2 ** 31 + 77), "--seconds", seconds],
                    devices=devices) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs(name, monkeypatch, capsys):
    out = run_cell(name, monkeypatch, capsys)
    assert list(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    names = {m["name"] for m in harness.cell(name).end_to_end}
    assert set(out["metrics"]) == names and "setup_s" in names
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_a_new_cell_is_new_files_only(tmp_path):
    """A copy of the benchmark with one more traffic file and one more entry
    in BENCHMARK.json runs the new cell with no code edited."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "rwkv6-1.6b.serve.tiny", "config": "rwkv6-1.6b",
                               "traffic": "serve.tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "rwkv6-1.6b.serve.longprompt" in m.get("workloads", []):
            m["workloads"].append("rwkv6-1.6b.serve.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    chip = root / "benchmarks" / "chip"
    (chip / "traffic" / "serve.tiny.json").write_text(json.dumps(
        {"driver": "serve_static", "batch": 2, "prompt_len": 16, "new_tokens": 4,
         "ahead_steps": 2, "check_sequences": 2, "check_block": 2}))
    shutil.copy(chip / "limits" / "rwkv6-1.6b.serve.longprompt.json",
                chip / "limits" / "rwkv6-1.6b.serve.tiny.json")
    code = (
        "import dataclasses, json, sys, jax, harness, smoke, run\n"
        "real = harness.cell\n"
        "harness.cell = lambda n: dataclasses.replace(\n"
        "    real(n), config=smoke.shrink(real(n)).config)\n"
        "sys.exit(run.main(['--workload', 'rwkv6-1.6b.serve.tiny', '--seed', '9', "
        "'--seconds', '1'], devices=jax.devices()))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(chip), str(harness.CHECKOUT / "src")]))
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                         timeout=600, cwd=root)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["attempted"] >= 2
    assert set(out["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}


# A configuration on the repo's mixtral-8x22b arch, every layer sparse as the
# program has it: one global attention layer, then two sliding-window ones,
# 4 of the arch's 8 experts held here, experts 512 wide beside a dense width
# of 1024.  Its reference is the program's own jnp forward: the test is of
# the harness's plumbing, not of a model.
NEW_CONFIG = {
    "name": "moe-two-groups", "source": "test", "arch": "mixtral-8x22b",
    "reference": "moe_two_groups", "num_hidden_layers": 3, "hidden_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "intermediate_size": 1024, "vocab_size": 1024, "rope_theta": 1e6,
    "groups": [{"pattern": ["attn"], "count": 1}, {"pattern": ["local"], "count": 2}],
    "program": {"n_experts": 4, "top_k": 2, "d_ff": 512, "window": 128},
    "reduced": ["num_hidden_layers", "n_experts"],
}
NEW_REFERENCE = '''
import dataclasses

import jax
import jax.numpy as jnp

import harness

SMALL = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=128, vocab_size=256,
             groups=[{"pattern": ["attn"], "count": 1}, {"pattern": ["local"], "count": 2}],
             program={"d_ff": 32, "window": 8})


def _cfg(conf, dtype):
    return dataclasses.replace(harness.program_config(conf), dtype=jnp.dtype(dtype).name)


def weights(conf, key, dtype=jnp.bfloat16):
    from repro.models import init_params

    return init_params(_cfg(conf, dtype), key)


def logits(conf, params, tokens, start, mode="f32"):
    from repro.models.transformer import forward

    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return forward(_cfg(conf, jnp.float32), params, tokens)[0][:, start:, :conf["vocab_size"]]
'''
NEW_COUNTS = '''
import harness


def serve_flops(conf, batch, prompt_len, new_tokens):
    active = harness.program_config(conf).active_param_count()
    return 2 * batch * (prompt_len + new_tokens - 1) * active


def kernels(conf, batch, prompt_len):
    return {}
'''


def test_program_config_of_groups_and_program_fields():
    """A layout of two groups; the experts' width beside the dense width."""
    from repro.configs.base import LayerGroup

    cfg = harness.program_config(NEW_CONFIG)
    assert cfg.groups == (LayerGroup(("attn",), 1), LayerGroup(("local",), 2))
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.window) == (4, 2, 512, 128)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (256, 4, 2, 64)
    same = dict(NEW_CONFIG, program=dict(NEW_CONFIG["program"], d_model=256))
    assert harness.program_config(same) == cfg  # a field that agrees is no error


def test_a_new_configuration_is_new_files_only(tmp_path):
    """A copy of the benchmark with a configuration of two layer groups and
    program fields, its reference with its SMALL widths, its counts, limits,
    traffic and cell, each added as new files and entries: the cell runs
    through run.main, shrunk by smoke.shrink, with no code edited."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    name = "moe-two-groups.serve.moe"
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "moe-two-groups", "source": "test", "why": "test",
                             "file": "benchmarks/chip/configs/moe-two-groups.json",
                             "reduced": NEW_CONFIG["reduced"]})
    bench["workloads"].append({"name": name, "config": "moe-two-groups", "traffic": "serve.moe",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "rwkv6-1.6b.serve.longprompt" in m.get("workloads", []) and "roofline" not in m["name"]:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (chip / "configs" / "moe-two-groups.json").write_text(json.dumps(NEW_CONFIG))
    (chip / "reference" / "moe_two_groups.py").write_text(NEW_REFERENCE)
    (chip / "counts" / "moe_two_groups.py").write_text(NEW_COUNTS)
    (chip / "traffic" / "serve.moe.json").write_text(json.dumps(
        {"driver": "serve_static", "batch": 8, "prompt_len": 512, "new_tokens": 64,
         "ahead_steps": 2, "check_sequences": 8, "check_block": 2}))
    (chip / "limits" / f"{name}.json").write_text(json.dumps({"max_gap": {"limit": 0.25}}))
    code = (
        "import sys, jax, harness, smoke, run\n"
        "from repro.configs.base import LayerGroup\n"
        "real = harness.cell\n"
        "harness.cell = lambda n: smoke.shrink(real(n))\n"
        f"cell = harness.cell({name!r})\n"
        "cfg = harness.program_config(cell.config)\n"
        "assert cfg.groups == (LayerGroup(('attn',), 1), LayerGroup(('local',), 2)), cfg\n"
        "assert (cfg.d_model, cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.window) == "
        "(64, 4, 2, 32, 8), cfg\n"
        "assert (cell.traffic['batch'], cell.traffic['prompt_len']) == (4, 32), cell.traffic\n"
        f"sys.exit(run.main(['--workload', {name!r}, '--seed', str(2 ** 31 + 78), "
        "'--seconds', '1'], devices=jax.devices()[:1]))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(chip), str(harness.CHECKOUT / "src")]))
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                         timeout=600, cwd=root)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 4
    assert set(out["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert out["checks"]["max_gap"]["value"] <= 0.25


ECHO_DRIVER = '''
import time

import numpy as np

import harness


class Driver:
    SPANS = ("echo.call",)

    def __init__(self, run):
        self.run, self.facts, self.attempted, self.failed = run, {}, 0, 0

    def setup(self):
        import jax
        import jax.numpy as jnp

        self.f = jax.jit(lambda x: x * 2.0)
        self.x = jnp.arange(float(self.run.cell.traffic["size"]))
        self.f(self.x).block_until_ready()

    def window(self):
        t0 = time.perf_counter()
        with self.run.profiled():
            while time.perf_counter() - t0 < self.run.seconds or not self.attempted:
                with harness.span("echo.call"):
                    y = self.f(self.x).block_until_ready()
                self.attempted += 1
        self.rate = self.attempted / (time.perf_counter() - t0)
        self.y = np.asarray(y)

    def end_to_end(self):
        return {"echo_calls_per_s": self.rate}

    def checks(self):
        err = float(np.abs(self.y - 2.0 * np.arange(self.y.size)).max())
        return [harness.Check("echo_err", err, self.run.cell.limits["echo_err"]["limit"])]
'''


def test_a_new_driver_is_new_files_only(tmp_path):
    """A configuration, a traffic mix, a driver with spans of its own, a
    per-layer metric and a cell, each added as new files and entries, run
    with no code edited; the traced run looks for the new driver's spans."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "echo", "source": "test", "reduced": [], "why": "test",
                             "file": "benchmarks/chip/configs/echo.json"})
    bench["workloads"].append({"name": "echo.small", "config": "echo", "traffic": "echo.small",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "echo_calls_per_s", "unit": "1/s", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": ["echo.small"]})
    bench["per_layer"].append({"name": "echo_spans", "unit": "1", "better": "higher",
                               "source": "device_trace", "layer": "device",
                               "moves": "echo_calls_per_s", "workloads": ["echo.small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (chip / "configs" / "echo.json").write_text(json.dumps({"name": "echo"}))
    (chip / "traffic" / "echo.small.json").write_text(json.dumps({"driver": "echo", "size": 8}))
    (chip / "limits" / "echo.small.json").write_text(json.dumps({"echo_err": {"limit": 0.0}}))
    (chip / "drivers" / "echo.py").write_text(ECHO_DRIVER)
    (chip / "metrics" / "echo_spans.py").write_text(
        "def read(view):\n    return view.trace.span_count('echo.call') or None\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(chip), str(harness.CHECKOUT / "src")]))

    def run(trace):
        code = ("import sys, jax, run\n"
                "sys.exit(run.main(['--workload', 'echo.small', '--seed', '3', '--seconds', "
                f"'0.2', '--trace', '{trace}'], devices=jax.devices()[:1]))\n")
        return subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                              text=True, timeout=300, cwd=root)

    res = run(0)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"echo_calls_per_s", "setup_s"}
    # A CPU trace has the host's spans and no TPU plane: the reduction finds
    # the new driver's spans, then stops where a chip's ops would be.
    res = run(1)
    assert res.returncode != 0 and "no TPU device plane" in res.stderr
    assert "no benchmark span" not in res.stderr


def test_a_directory_with_only_the_benchmark_fails(tmp_path):
    """Without the program beside it, a run exits non-zero and prints no result."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    shutil.copy(harness.CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
                         env=env, capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""


@pytest.mark.parametrize("ahead", [0, 2, 64])
def test_serve_reads_every_token_once_in_order(ahead, monkeypatch):
    """However many steps are dispatched ahead of the readback, a batch's
    tokens are those of the step-by-step loop, each stamped once, in order."""
    import dataclasses

    smoke.use(harness, monkeypatch)
    base = harness.cell("olmo-1b.serve.chat")
    tokens = {}
    for k in (0, ahead):
        cell = dataclasses.replace(base, traffic=dict(base.traffic, ahead_steps=k))
        driver = harness.load_module("drivers", "serve_static").Driver(
            harness.Run(cell, 11, 0.0, False, jax.devices()[:1]))
        driver.setup()
        batch = driver._batch(0)
        assert batch.tokens.shape == (driver.B, driver.N)
        assert len(batch.stamps) == driver.N and (batch.stamps[1:] >= batch.stamps[:-1]).all()
        tokens[k] = batch.tokens
    assert (tokens[0] == tokens[ahead]).all()
