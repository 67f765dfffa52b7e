"""What every cell shares: finding its files by name, the device, the
compile clock, the run's records and the result line.

A cell is ``workloads/<cell>.json``; it names a configuration
(``configs/<config>.json``), a traffic mix (``traffic/<mix>.json``, read
by the generator ``traffic/<kind>.py`` that the mix names) and the
engine's settings.  Each metric is ``metrics/<metric>.py``.  What is
particular to an architecture is ``arch/<class>.py``, found by the class
the configuration's ``architectures`` names (see ``arch``).  Nothing here
names a cell, a configuration, an architecture or a metric.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SPEC_FILE = ROOT / "BENCHMARK.json"


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _named(kind: str, name: str, suffix: str = ".json") -> Path:
    path = HERE / kind / f"{name}{suffix}"
    if not path.is_file():
        raise SystemExit(f"no {kind[:-1]} named {name!r} ({path} missing)")
    return path


def load_cell(name: str) -> Dict[str, Any]:
    cell = load_json(_named("workloads", name))
    if cell["name"] != name:
        raise SystemExit(f"{name}: its file names itself {cell['name']!r}")
    return cell


def load_config(name: str) -> Dict[str, Any]:
    conf = load_json(_named("configs", name))
    if conf["name"] != name:
        raise SystemExit(f"{name}: its file names itself {conf['name']!r}")
    return conf


def load_mix(name: str) -> Dict[str, Any]:
    return load_json(_named("traffic", name))


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module; names may hold dots.  A file is
    run once per process, so what it compiles is compiled once."""
    path = _named(kind, name, ".py")
    if path not in _LOADED:
        mod_name = f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


_LOADED: Dict[Path, Any] = {}


def arch(conf: Dict[str, Any]):
    """The module of the configuration's published class,
    ``arch/<architectures[0]>.py``.  It supplies everything that differs
    between architectures, each taking the configuration first:

    - weights: ``shapes(conf)``, the parameter tree (leaf shapes, the
      layers' leaves stacked under ``layers``; ``embed``, ``final_norm``
      and, untied, ``lm_head`` at the top), and ``scale(name, shape,
      conf)``, the standard deviation of a layer leaf, or None for a
      norm's gain;
    - reference: ``layer_statics(conf, i)``, layer ``i``'s hashable
      settings (window, rope), and ``layer(x, lw, *, bf16x3, **statics)``,
      one layer's float32 forward over a whole sequence;
    - program bridge: ``model_config(conf)`` and ``params(conf, w)``;
    - work counts: ``dims(conf)``, ``matmul_params(conf)``,
      ``attention_flops(conf, ctx_rows, slots)`` and
      ``paged_attention(conf, rows, ctx_rows, kv_rows, slots)``, and any
      other count a roofline metric divides by (``work.count``).
    """
    classes = conf.get("architectures")
    if not classes:
        raise SystemExit(f"{conf.get('name')!r}: the configuration names "
                         "no class under 'architectures'")
    return load_module("arch", classes[0])


def cell_metrics(spec: Dict[str, Any], cell: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metrics that ``spec`` gives ``cell``.

    An end-to-end metric without a ``workloads`` key belongs to every
    cell; a per-layer one without it to every cell that reports the
    end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": layer}


# -- the device ------------------------------------------------------------


def require_chips(n: int) -> None:
    """Fail unless JAX's first device is a TPU and it sees ``n`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r}; this benchmark runs only "
                         "on the chip")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX sees {len(devs)}")


def device_facts(n: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()[:n]
    peak, limit = 0, 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        limit = max(limit, int(stats.get("bytes_limit", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak,
            "bytes_limit": limit}


class CompileClock:
    """Backend compiles and their seconds, from JAX's own events."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


# -- what a run records ----------------------------------------------------


@dataclass
class Round:
    """One ``engine.step()`` inside the window, as the harness saw it."""
    t0: float
    t1: float
    running: int              # slots held after the step
    pages_used: int           # pool pages held after the step
    rows: int                 # query rows computed (prefill + decode)
    ctx_rows: int             # sum over rows of the context each attends
    kv_rows: int              # sum over slots of the K/V rows read
    # (live length, rows computed) of each slot that computed rows: its
    # rows attend contexts live - rows + 1 .. live, and it reads its live
    # K/V rows.  What a count clipped to a window needs.
    slots: Tuple[Tuple[int, int], ...] = ()


@dataclass
class Sent:
    """One request the traffic sent: when it was due and what it is."""
    due: float
    prompt: Any               # np.ndarray of ids
    max_new: int
    req: Any = None           # the program's Request once submitted
    sent: Optional[float] = None
    group: int = -1


@dataclass
class RunRecord:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    seed: int
    max_batch: int = 0
    num_blocks: int = 0
    t_start: float = 0.0
    t_end: float = 0.0
    rounds: List[Round] = field(default_factory=list)
    sent: List[Sent] = field(default_factory=list)
    tokens_out: int = 0       # tokens the engine emitted in the window
    histograms: Dict[str, List[float]] = field(default_factory=dict)
    trace: Any = None         # xplane.Reduced of a --trace 1 run
    peaks: Dict[str, float] = field(default_factory=dict)
    facts: Dict[str, Any] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start


def emit(result: Dict[str, Any], checks: List[Dict[str, Any]]) -> None:
    """The compared numbers on standard error, then the result line."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.monotonic()
