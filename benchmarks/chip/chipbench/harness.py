"""What every cell shares: finding its files by name, seeds, host spans,
the device check and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness finds them as ``configs/<config>.json`` and
``traffic/<traffic>.json``, the limits of its correctness comparison as
``limits/<cell>.json``, each per-layer metric as ``metrics/<metric>.py``
and the model family a configuration names (its ``family`` key) as
``families/<family>.py``. A later cell, metric or architecture is added by
adding files.
"""
from __future__ import annotations

import contextlib
import functools
import importlib.util
import json
import os
import time
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))
COMPILE_CACHE = os.path.join(CHECKOUT, ".jax_cache")


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file)."""


def _read_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing file: {path}") from None


def load_benchmark(root: str = CHECKOUT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


@dataclass
class Cell:
    """One entry of ``workloads`` with everything found by its names."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: Dict[str, Any], name: str,
              bench_dir: str = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    limits = _read_json(os.path.join(bench_dir, "limits", f"{name}.json"))
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(os.path.join(bench_dir, "configs",
                                       f"{w['config']}.json")),
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        f"{w['traffic']}.json")),
        limits={k: float(v) for k, v in limits["limits"].items()},
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def _exec_file(path: str, prefix: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench_dir: str = BENCH_DIR
                ) -> Callable[["RunRecord"], Optional[float]]:
    """``metrics/<metric>.py``'s ``read(record)``: the metric's value, or
    None where the run holds nothing for it to read."""
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    if not os.path.exists(path):
        raise BenchError(f"no reader for metric {metric!r}: {path}")
    return _exec_file(path, "chipbench_metric", metric).read


def load_family(name: str, bench_dir: Optional[str] = None) -> ModuleType:
    """``families/<name>.py``: one model family's ``layout(c)``,
    ``logits(c, params, tokens, precision)``, ``forward_per_token(c,
    seq)`` and ``model_config(c)``, and optionally ``leaf_init`` and
    ``loss_sum`` in place of ``chipbench.reference``'s. ``bench_dir``
    defaults to this module's ``BENCH_DIR``, read at the call."""
    path = os.path.join(bench_dir or BENCH_DIR, "families", f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no family file for {name!r}: {path}")
    return _family_module(path, name)


@functools.lru_cache(maxsize=None)
def _family_module(path: str, name: str) -> ModuleType:
    return _exec_file(path, "chipbench_family", name)


def peaks_for(device_kind: str, bench_dir: str = BENCH_DIR
              ) -> Dict[str, float]:
    """The published peaks of one chip. A device missing from the table is
    an error: no metric is computed against a guessed peak."""
    table = _read_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json"
                         f" (known: {sorted(table)})")
    return table[device_kind]


# ------------------------------------------------------------------ seeds
def seed_words(seed: int, stream: int = 0) -> Tuple[int, int]:
    """Two 32-bit words from any non-negative seed (also above 2**32) and
    a stream number: every bit of the seed counts."""
    if seed < 0:
        raise BenchError(f"--seed must be >= 0, got {seed}")
    w = np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)
    return int(w[0]), int(w[1])


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, *stream])


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans taken by the benchmark around the program's calls.

    Each span is also a ``jax.profiler.TraceAnnotation``, so a traced run
    sees it on the device trace's clock, where the trace reduction uses it
    to say what the host was doing in a gap of the device."""

    def __init__(self):
        self.done: Dict[str, List[float]] = {}
        self.marks: Dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.add(name, time.perf_counter() - t0)

    def add(self, name: str, seconds: float) -> None:
        self.done.setdefault(name, []).append(seconds)

    def mark(self, name: str) -> float:
        self.marks[name] = t = time.perf_counter()
        return t


class _CompileCounter:
    """Backend compiles in this process, from JAX's monitoring events. JAX
    records its compile event also where it loads the program from the
    persistent cache, and a cache-hit event beside it: a compile is an
    event without a hit."""
    events = hits = 0
    listening = False

    @classmethod
    def on_duration(cls, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cls.events += 1

    @classmethod
    def on_event(cls, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cls.hits += 1


def compile_count() -> int:
    import jax
    if not _CompileCounter.listening:
        jax.monitoring.register_event_duration_secs_listener(
            _CompileCounter.on_duration)
        jax.monitoring.register_event_listener(_CompileCounter.on_event)
        _CompileCounter.listening = True
    return _CompileCounter.events - _CompileCounter.hits


# ----------------------------------------------------------------- result
@dataclass
class Compared:
    """One number of the correctness comparison beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclass
class RunRecord:
    """What a driver hands back: end-to-end values, spans, counters, the
    trace summary of a traced run, and what the comparison read."""
    end_to_end: Dict[str, float] = field(default_factory=dict)
    spans: Spans = field(default_factory=Spans)
    counters: Dict[str, Any] = field(default_factory=dict)
    trace: Any = None
    compared: List[Compared] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    cell: Optional[Cell] = None
    peaks: Optional[Dict[str, float]] = None
    chips: int = 1

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(c.ok for c in self.compared) \
            and self.failed == 0


def result_line(rec: RunRecord, traced: bool, device: Dict[str, Any],
                units: Dict[str, str], values: Dict[str, float]
                ) -> Dict[str, Any]:
    out = {
        "correct": rec.correct,
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in values.items()},
        "device": device,
    }
    if traced and rec.trace is not None:
        out["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                            "idle_gaps": rec.trace.top_gaps(10)}
    out["compared"] = {c.name: {"value": float(c.value), "limit": c.limit}
                       for c in rec.compared}
    return out
