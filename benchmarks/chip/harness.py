"""Shared machinery of the on-chip benchmark.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
under a traffic mix.  Everything that belongs to one of them lives in a file
of its own under this directory, found by the names ``BENCHMARK.json`` gives:

    configs/<config>.json     published sizes, source, repo arch, reference
    traffic/<traffic>.json    the driver and the mix's parameters
    limits/<cell>.json        the limit of each number ``correct`` compares
    drivers/<driver>.py       one per kind of traffic, with the names of the
                              profiler spans it wraps its calls in (``SPANS``)
                              and the small sizes of its mix (``SMALL``)
    metrics/<metric>.py       one reader per per-layer metric
    reference/<name>.py       plain float32 reference, seeded weights, and the
                              small widths of the CPU tests (``SMALL``)
    counts/<name>.py          operations and bytes computed from shapes

A new configuration brings its file, its reference and counts, its limits
and traffic; its file says everything the program needs (``program_config``):

    published keys            those of ``_PROGRAM_FIELDS`` map one to one
    "program": {...}          fields of the program's ``ModelConfig`` as they
                              are run: the experts held on this chip
                              (``n_experts``, also in ``reduced``), ``top_k``,
                              the experts' ``d_ff``, ``window``, ...
    "groups": [{...}, ...]    the layer layout, each entry a ``LayerGroup``
                              (``pattern`` of the program's layer kinds,
                              ``count``); without it, the arch's first group
                              repeated to ``num_hidden_layers``

So a later cell, configuration or metric is new files and new entries, and
no edit here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under this directory, loaded once per process."""
    root = HERE
    key = f"chipbench.{kind}.{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, root / kind / f"{name}.py")
        if spec is None or not (root / kind / f"{name}.py").is_file():
            raise FileNotFoundError(root / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    limits: dict  # limits/<cell>.json
    end_to_end: List[dict]  # BENCHMARK.json metrics this cell reports untraced
    per_layer: List[dict]  # ... and traced


def cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files."""
    root = HERE
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    limits_path = root / "limits" / f"{name}.json"
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=load_json(root / "configs" / f"{entry['config']}.json"),
        traffic=load_json(root / "traffic" / f"{entry['traffic']}.json"),
        limits=load_json(limits_path) if limits_path.is_file() else {},
        end_to_end=e2e,
        per_layer=per_layer,
    )


def require_devices(chips: int):
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found {devices}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


# Keys of a configuration file -> fields of the program's ModelConfig.
_PROGRAM_FIELDS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_size": "rwkv_head_dim",
    "rope_theta": "rope_theta",
}


def program_config(conf: dict):
    """The program's ModelConfig for ``conf``: its repo arch, with every
    size the file states.  The file is the configuration as it is run.

    A ``"program"`` field that is no field of ``ModelConfig``, or that
    disagrees with a published key mapped to the same field, is an error,
    and so is a ``"groups"`` list whose layers do not add up to
    ``num_hidden_layers``.  One field may differ: where the run has experts,
    ``d_ff`` is their width and ``intermediate_size`` may state the dense
    layers' width beside it."""
    from repro.configs import get_config
    from repro.configs.base import LayerGroup, ModelConfig

    base = get_config(conf["arch"])
    fields = {f: conf[k] for k, f in _PROGRAM_FIELDS.items() if k in conf}
    program = conf.get("program", {})
    known = {f.name for f in dataclasses.fields(ModelConfig)} - {"groups"}
    if set(program) - known:
        raise ValueError(f"{conf['name']}: {sorted(set(program) - known)} in \"program\" "
                         f"are no fields of ModelConfig (the layout is \"groups\")")
    experts = program.get("n_experts", base.n_experts) > 0
    clash = sorted(f for f, v in program.items() if f in fields and fields[f] != v
                   and not (f == "d_ff" and experts))
    if clash:
        raise ValueError(f"{conf['name']}: \"program\" disagrees with the published keys "
                         f"on {clash}")
    fields.update(program)
    if "groups" in conf:
        groups = tuple(LayerGroup(**dict(g, pattern=tuple(g["pattern"]))) for g in conf["groups"])
        layers = sum(g.n_layers for g in groups)
        if layers != conf["num_hidden_layers"]:
            raise ValueError(f"{conf['name']}: \"groups\" hold {layers} layers, "
                             f"num_hidden_layers is {conf['num_hidden_layers']}")
    else:
        group = base.groups[0]
        groups = (dataclasses.replace(
            group, count=conf["num_hidden_layers"] // len(group.pattern)),)
    return dataclasses.replace(base, groups=groups, **fields)


class CompileCounter:
    """Counts the programs XLA built or loaded, and those of them that came
    from the persistent cache; the rest were compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.count, self.hits, self.seconds = 0, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1


def configure_jax() -> str:
    """Persistent compile cache at the program's fixed place; cache every
    program, however quick its compile, so that a warm run compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Check:
    """One number ``correct`` compares: it passes while value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit  # NaN fails


@dataclasses.dataclass
class Run:
    """What one process of the benchmark was asked to do."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    trace_dir: Path = HERE / ".runs" / "trace"

    @contextlib.contextmanager
    def profiled(self, on: bool = True):
        """Profile the block when this is a traced run (and ``on``)."""
        if not (self.trace and on):
            yield
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        with jax.profiler.trace(str(self.trace_dir), profiler_options=opts):
            yield


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def layout(tree):
    """Shapes and dtypes of a parameter tree, comparable with ``==``."""
    import jax

    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def print_checks(checks: List[Check]) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)


def result(correct: bool, attempted: int, failed: int, metrics: Dict[str, dict],
           device: dict, checks: List[Check], breakdown: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return json.dumps(out)
